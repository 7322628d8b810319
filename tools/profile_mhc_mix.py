"""Time the hyper-connection mixes alone, on the chip: the maps (norm,
projection, sigmoids, Sinkhorn), the pre-mix and the post-mix of
``ops/hyper_connections.py`` around an identity sublayer, chained through
``--mixes`` sublayers in one ``lax.scan`` over stacked leaves, at a decode
step's shape (``--rows`` x 1 token) and a prefill dispatch's (1 x ``--seq``).

    python tools/profile_mhc_mix.py [--hidden 3584] [--mult 4] [--iters 20]
        [--rows 32] [--seq 2048] [--mixes 26] [--trips 4 20]
        [--precision highest default]

A JSON line a case: ``busy_ms`` a mix (device 0's busy time over the traced
calls, a mix), ``span_ms`` a mix (first operation's start to the last one's
end: with the launches' gaps), ``ops`` a mix (device operations), the
stream's least bytes a mix (``(3n + 1) C`` values) over ``span_ms``
(``gb_s``), and the largest operations. ``--trips`` sets the Sinkhorn rounds
a trip of its loop (``hyper_connections.ROUNDS_A_TRIP``), ``--precision`` the
maps' projection. Device time is from a profiler trace (``utils/xplane.py``),
never a host clock.

Import the package from another checkout with ``PYTHONPATH=<root>`` to time
that checkout on the same chip.
"""
import argparse
import json
import os
import sys
import tempfile
from unittest import mock

sys.path.append(os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp

from distributed_llm_inference_tpu.config import HyperConnectionConfig
from distributed_llm_inference_tpu.ops import hyper_connections as mhc
from distributed_llm_inference_tpu.utils.xplane import aggregate, find_xplane


def traced(fn, *args, reps=3):
    """``aggregate`` of ``reps`` calls of ``fn``, compiled beforehand."""
    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as td:
        with jax.profiler.trace(td):
            for _ in range(reps):
                out = fn(*args)
            jax.block_until_ready(out)
        return aggregate(find_xplane(td))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--hidden", type=int, default=3584)
    ap.add_argument("--mult", type=int, default=4)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--rows", nargs="+", type=int, default=[32])
    ap.add_argument("--seq", nargs="+", type=int, default=[2048])
    ap.add_argument("--mixes", type=int, default=26)
    ap.add_argument("--trips", nargs="+", type=int, default=[mhc.ROUNDS_A_TRIP])
    ap.add_argument("--precision", nargs="+", default=["highest"],
                    choices=["highest", "high", "default"])
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit("a device time comes from a chip: no TPU here")

    hc = HyperConnectionConfig(mult=args.mult, sinkhorn_iters=args.iters)
    n, c = args.mult, args.hidden
    shapes = mhc.leaf_shapes(hc, c)
    key = jax.random.PRNGKey(args.seed)
    p = {
        "hc_phi": jax.random.normal(key, (args.mixes, *shapes["phi"]), jnp.float32)
        * shapes["phi"][1] ** -0.5,
        "hc_alpha": jnp.ones((args.mixes, 3), jnp.float32),
        "hc_bias": jax.random.normal(
            jax.random.fold_in(key, 1), (args.mixes, *shapes["bias"]), jnp.float32
        ),
    }
    least = (3 * n + 1) * c * 2      # bytes a token a mix, bf16

    def chain_fn():
        # a function of its own a setting: jit's cache is keyed by it
        def chain(p, x):
            def mix(x, lp):
                h, maps = mhc.pre_mix(hc, lp, "hc", x, 1e-6)
                return mhc.post_mix(x, h, maps), None   # sublayer: identity

            return jax.lax.scan(mix, x, p)[0]

        return jax.jit(chain)

    cases = [(r, 1) for r in args.rows] + [(1, s) for s in args.seq]
    einsum = jnp.einsum
    for precision in args.precision:
        for trips in args.trips:
            with mock.patch.object(mhc, "ROUNDS_A_TRIP", trips), mock.patch.object(
                jnp, "einsum",
                lambda *a, precision=None, _p=precision, **kw: einsum(
                    *a, precision=_p, **kw
                ),
            ):
                fn = chain_fn()
                for rows, seq in cases:
                    x = jax.random.normal(
                        jax.random.fold_in(key, 2), (rows, seq, n, c), jnp.bfloat16
                    )
                    agg = traced(fn, p, x, reps=args.reps)
                    dev = agg["devices"][0]
                    calls = args.reps * args.mixes
                    # the reps follow each other on the device: the span
                    # holds the launches' gaps, and a dispatch's between reps
                    span = (dev["last_ns"] - dev["first_ns"]) / calls
                    top = sorted(agg["ops_ns"].items(), key=lambda kv: -kv[1])[:5]
                    print(json.dumps({
                        "rows": rows, "seq": seq, "rounds_a_trip": trips,
                        "precision": precision,
                        "busy_ms": dev["busy_ns"] / calls / 1e6,
                        "span_ms": span / 1e6,
                        "ops": sum(agg["op_counts"].values()) / calls,
                        "gb_s": round(least * rows * seq / span, 1),
                        "top": [(k[:60], round(v / calls / 1e3, 2)) for k, v in top],
                    }), flush=True)


if __name__ == "__main__":
    main()
