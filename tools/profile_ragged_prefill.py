"""Time the ragged prefill kernel alone, on the chip, by how much of its
dispatch is valid.

    python tools/profile_ragged_prefill.py [--kernels int8 latent]
        [--rows 1 2] [--widths 38 47 59] [--valid 64 256 1024 2048]
        [--pad 2048] [--window 4096]

``quantized_ragged_paged_attention`` at Mistral-7B widths (32 q / 8 kv heads
of 128, 32 layers) and ``quantized_latent_ragged_paged_attention`` at
Moonlight's (16 heads over one 576-wide int8 latent, 16 layers), 64-token
pages, over a seeded int8 pool: one jitted pass = the layers' calls in a
row, as a prefill dispatch of ``rows`` prompts of ``valid`` tokens each,
padded to ``pad``, over a table ``width`` pages wide. Device time is the sum
of the kernel's events in a profiler trace (``utils/xplane.py``), never a
host clock.

A line a case: ms a layer, the grid's steps and how many of them hold a live
(query, key) pair, counted HERE from the element mask and not by the
program. Then a line a (kernel, rows, width): the least-squares cost of a
live and of a dead step over that line's cases (a kernel that runs every
step alike reads the same for both). ``sha1`` is of the summed outputs'
bytes: two checkouts that agree bit for bit print the same.

Import the package from another checkout with ``PYTHONPATH=<root>`` to time
that checkout's kernel on the same chip: the tool uses nothing else of the
repository but ``utils/xplane.py``.
"""
import argparse
import hashlib
import json
import os
import sys
import tempfile

sys.path.append(os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from distributed_llm_inference_tpu.ops import ragged_attention as ra
from distributed_llm_inference_tpu.utils.xplane import aggregate, find_xplane

PS = 64
# kernel -> (event name, q heads, pool heads, stored width, layers)
SHAPES = {
    "int8": ("quantized_ragged_paged_attention", 32, 8, 128, 32),
    "latent": ("quantized_latent_ragged_paged_attention", 16, 1, 576, 16),
}


def live_tiles(valid, pad, block_q, width, window):
    """Tiles (q-block, page) of one row's grid with a pair the element mask
    keeps, for a row of ``valid`` tokens from position 0: brute force."""
    q = np.arange(pad)[:, None]
    k = np.arange(width * PS)[None, :]
    keep = (k < valid) & (k <= q) & (q < valid)
    if window:
        keep &= k > q - window
    return int(keep.reshape(pad // block_q, block_q, width, PS)
               .any(axis=(1, 3)).sum())


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--kernels", nargs="+", default=["int8", "latent"],
                    choices=sorted(SHAPES))
    ap.add_argument("--rows", nargs="+", type=int, default=[1, 2])
    ap.add_argument("--widths", nargs="+", type=int, default=[38, 47, 59])
    ap.add_argument("--valid", nargs="+", type=int,
                    default=[64, 256, 1024, 2048])
    ap.add_argument("--pad", type=int, default=2048)
    ap.add_argument("--window", type=int, default=4096)
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit("a device time comes from a chip: no TPU here")
    window = args.window or None
    pages = max(args.rows) * max(args.widths) + 1

    for kind in args.kernels:
        name, hq, hkv, d, layers = SHAPES[kind]
        kp, ks, kq = jax.random.split(jax.random.PRNGKey(args.seed), 3)

        @jax.jit
        def make(kp, ks):
            shape = (layers, pages, hkv, PS, d)
            k = jax.random.randint(kp, shape, -127, 128, jnp.int8)
            v = jax.random.randint(ks, shape, -127, 128, jnp.int8)
            s = jax.random.uniform(ks, shape[:-1], jnp.float32, 0.01, 0.03)
            return k, s, v, s + 0.001

        pool = make(kp, ks)

        @jax.jit
        def run(pool, q, table, lens):
            def layer(acc, planes):
                k, ks, v, vs = planes
                if kind == "latent":
                    out = ra.quantized_latent_ragged_paged_attention(
                        q, k, ks, table, lens, lens, sliding_window=window,
                    )
                else:
                    out = ra.quantized_ragged_paged_attention(
                        q, k, ks, v, vs, table, lens, lens,
                        sliding_window=window,
                    )
                return acc + out.astype(jnp.float32), None

            return jax.lax.scan(
                layer, jnp.zeros(q.shape, jnp.float32), pool
            )[0]

        for rows in args.rows:
            q = jax.random.normal(kq, (rows, args.pad, hq, d), jnp.bfloat16)
            block_q = ra._prep(
                q, jax.ShapeDtypeStruct(pool[0].shape[1:], jnp.int8), None
            )[4]
            for width in args.widths:
                table = jnp.asarray(
                    1 + np.arange(rows * width).reshape(rows, width), jnp.int32
                )
                cases = []
                for valid in args.valid:
                    if valid > min(args.pad, width * PS):
                        continue
                    lens = jnp.full((rows,), valid, jnp.int32)
                    acc = jax.block_until_ready(run(pool, q, table, lens))
                    with tempfile.TemporaryDirectory() as td:
                        with jax.profiler.trace(td):
                            for _ in range(args.reps):
                                acc = run(pool, q, table, lens)
                            jax.block_until_ready(acc)
                        agg = aggregate(find_xplane(td))
                    ns = sum(v for k, v in agg["ops_ns"].items() if name in k)
                    calls = sum(
                        v for k, v in agg["op_counts"].items() if name in k
                    )
                    steps = rows * (args.pad // block_q) * width
                    live = rows * live_tiles(
                        valid, args.pad, block_q, width, window
                    )
                    ms = ns / max(calls, 1) / 1e6
                    cases.append((live, steps - live, ms))
                    print(json.dumps({
                        "kernel": name, "rows": rows, "pad": args.pad,
                        "width": width, "valid": valid, "block_q": block_q,
                        "grid_steps": steps, "live_steps": live,
                        "kernel_calls": calls,
                        "ms_a_layer": round(ms, 4),
                        "ms_a_pass": round(ms * layers, 3),
                        "us_a_step": round(1e3 * ms / steps, 4),
                        "sha1": hashlib.sha1(
                            np.asarray(acc).tobytes()
                        ).hexdigest()[:12],
                        "device": jax.devices()[0].device_kind,
                    }), flush=True)
                if len(cases) >= 2:
                    a = np.asarray([c[:2] for c in cases], np.float64)
                    y = np.asarray([c[2] for c in cases], np.float64) * 1e3
                    (us_live, us_dead), *_ = np.linalg.lstsq(a, y, rcond=None)
                    print(json.dumps({
                        "kernel": name, "rows": rows, "width": width,
                        "fit_us_a_live_step": round(float(us_live), 4),
                        "fit_us_a_dead_step": round(float(us_dead), 4),
                    }), flush=True)
        del pool


if __name__ == "__main__":
    main()
