"""Time the in-place paged decode kernel alone, on the chip, by occupancy.

    python tools/profile_paged_sweep.py [--rows 32] [--width 47] [--pages 1600]
        [--window 4096] [--occupancy full reason chat window3 select182]
        [--form inplace|gathered|latent|latent32|latent-grid]
        [--blocks 4 8 16]

``quantized_paged_fused_attention`` at Mistral-7B widths (32 q / 8 kv heads
of 128, 64-token pages, 32 layers, a 16-slot tail) over a seeded int8 pool,
one jitted "decode step" = the 32 layers' calls in a row, the tail planes
carried through as the engine's scan carries them. Device time is the sum of
the kernel's events in a profiler trace (``utils/xplane.py``), never a host
clock. ``--occupancy``: ``full`` every page of every row live; ``reason`` 32
live rows a quarter full (lengths spread about a mean of 657); ``chat`` 5% of
the table's positions live, in a few rows, the other slots empty; a number is
that share of every row's table, in per cent. An empty slot has nothing
valid in its tail, which is what has the kernel skip it (the engine leaves a
released slot's length stale; the kernel does not read it). Two more bring
their own shapes (``CASES``): ``window3`` a window layer's pool of
``k-exaone-236b-a23b.mixedlen`` (32 rows of 1.5-6k tokens on a 227-slot
table under a window of 128: 3 live pages a row) and ``select182``
``keye-vl2-30b-a3b.longdoc``'s sweep (16 rows of 4-10k tokens, 4 kv heads, a
182-slot table, 2048 positions a row selected at random).

``--blocks``: the pages a block of the sweep (``_pages_per_block``) forced
to each width in turn, a line a width with ``kernel_us_a_live_page``; left
out, the kernel picks its own (``pages_a_block`` says which).

``--form gathered`` times what ``QuantizedPagedKVCache`` runs UNDER
``INPLACE_CTX`` instead: ``quantized_fused_decode_attention`` over every
row's table span gathered to contiguous stacks, and the gather itself (once
a fused window of ``KT`` steps, so a ``KT``-th of it belongs to a step).

``--form latent`` times the one-stored-plane form of the same kernel at
Moonlight's widths (16 query heads on one latent head of 576, 16 layers: an
int8 latent engine's decode step, ``quantized_latent_paged_fused_attention``),
``--form latent32`` the same at Xing4.0's (32 query heads, 13 layers),
and ``--form latent-grid`` what such an engine ran before it had a tail: the
``(slots, table width)`` grid of ``quantized_latent_paged_attention`` over a
layer's slice of the pool (the slice's copy is in ``busy_ms_a_step``).
``--occupancy reason1k``: 32 live rows of 1.5-3k tokens, that cell's mix.
``steps_walked`` / ``steps_grid`` count a call's grid steps where the pool
is swept by pipelined blocks (a row's blocks that hold a live page, one for a
row with none, against rows x table blocks), ``kernel_us_a_walked_step`` the
kernel's time over the first.

Import the package from another checkout with ``PYTHONPATH=<root>`` to time
that checkout's kernel on the same chip: the tool itself uses nothing else
of the repository but ``utils/xplane.py``. ``busy_ms_a_step`` is the whole
step's device time: the kernel and what its wrapper prepares for it a layer
(``beside_kernel_ms_a_step`` is that alone, ``beside_kernel_top`` its largest
operations by the trace's names, ms a step: before PR 61 the gather of every
table slot's scale rows). What a checkout prepares once a window instead
(``pa.joined_scale_rows``: K's and V's scale rows of a page side by side in
one plane, which the kernel then copies by the live page) is timed apart,
``join_ms_a_window``, as ``--form gathered``'s gather is: a ``KT``-th of it
belongs to a step.
"""
import argparse
import hashlib
import json
import os
import sys
import tempfile
import time

sys.path.append(os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from distributed_llm_inference_tpu.ops import paged_attention as pa
from distributed_llm_inference_tpu.ops import quant_attention as qa
from distributed_llm_inference_tpu.utils.xplane import aggregate, find_xplane

PS, KT = 64, 16
# occupancy -> what it overrides of the command line's shapes
CASES = {
    "window3": dict(rows=32, width=227, window=128, pages=512, layers=9),
    "select182": dict(rows=16, width=182, window=0, pages=3072, hkv=4,
                      layers=12, select=2048),
}
# form -> query heads, kv heads, stored width, layers, the kernel's trace name
FORMS = {
    "inplace": (32, 8, 128, 32, "quantized_paged_fused_attention"),
    "gathered": (32, 8, 128, 32, "quantized_fused_decode_attention"),
    "latent": (16, 1, 576, 16, "quantized_latent_paged_attention"),
    "latent32": (32, 1, 576, 13, "quantized_latent_paged_attention"),
    # "..._grid_attention" since the fused form took the name
    "latent-grid": (16, 1, 576, 16, "quantized_latent_paged_"),
}


def row_lengths(kind, rows, width, rng):
    cap = width * PS
    if kind == "full":
        return np.full(rows, cap, np.int32)
    if kind == "reason":
        return np.minimum(rng.integers(214, 1100, rows), cap).astype(np.int32)
    if kind == "reason1k":
        return np.minimum(rng.integers(1536, 3072, rows), cap).astype(np.int32)
    if kind == "window3":
        return np.minimum(rng.integers(1536, 6144, rows), cap).astype(np.int32)
    if kind == "select182":
        return np.minimum(rng.integers(4096, 10240, rows), cap).astype(np.int32)
    if kind == "chat":
        lens = np.zeros(rows, np.int32)
        live = max(1, rows // 4)
        lens[rng.permutation(rows)[:live]] = int(0.05 * rows * cap / live)
        return np.minimum(lens, cap)
    return np.full(rows, int(float(kind) / 100 * cap), np.int32)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=32)
    ap.add_argument("--width", type=int, default=47)
    ap.add_argument("--pages", type=int, default=1600)
    ap.add_argument("--window", type=int, default=4096)
    ap.add_argument("--occupancy", nargs="+", default=["full", "reason", "chat"])
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--form", choices=tuple(FORMS), default="inplace")
    ap.add_argument("--blocks", nargs="+", type=int, default=[0])
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit("a device time comes from a chip: no TPU here")
    pick = pa._pages_per_block
    for kind in args.occupancy:
        for block in args.blocks:
            pa._pages_per_block = (
                (lambda *a, **k: block) if block else pick
            )
            run_case(args, kind, pick)


def run_case(args, kind, pick):
    HQ, HKV, D, LAYERS, kernel = FORMS[args.form]
    latent = args.form.startswith("latent")
    case = CASES.get(kind, {})
    if case and args.form != "inplace":
        sys.exit(f"{kind} is a case of the in-place form")
    b, t = case.get("rows", args.rows), case.get("width", args.width)
    pages = case.get("pages", args.pages)
    HKV, LAYERS = case.get("hkv", HKV), case.get("layers", LAYERS)
    window = None if latent else case.get("window", args.window) or None
    key = jax.random.PRNGKey(args.seed)
    kk, kv, ks, kq = jax.random.split(key, 4)

    @jax.jit
    def make(kk, kv, ks):
        shape = (LAYERS, pages, HKV, PS, D)
        k = jax.random.randint(kk, shape, -127, 128, jnp.int8)
        v = jax.random.randint(kv, shape, -127, 128, jnp.int8)
        s = jax.random.uniform(ks, shape[:-1], jnp.float32, 0.01, 0.03)
        return (k, s) if latent else (k, s, v, s + 0.001)

    pool = make(kk, kv, ks)
    q = jax.random.normal(kq, (b, 1, HQ, D), jnp.bfloat16)
    new = jax.random.normal(kq, (b, 1, HKV, D), jnp.bfloat16)

    @jax.jit
    def gather(pool, table):  # cache/paged.py tail_big_stacks, kernel order
        def g(pages):
            v = jnp.moveaxis(jnp.take(pages, table, axis=1), 3, 2)
            return v.reshape(v.shape[:3] + (t * PS,) + v.shape[5:])

        return tuple(g(p) for p in pool)

    @jax.jit
    def step(pool, tails, table, lens, vlen, select):
        # the walk of the sweep, built outside the layers' loop as the
        # engine builds it (models/llama.py; there once a window of 16
        # steps); a checkout from before PR 48 has none
        walk = {}
        if args.form in ("latent", "latent32") and hasattr(
            pa, "latent_sweep_walk"
        ):
            walk["walk"] = pa.latent_sweep_walk(pool[0], KT, table, lens, vlen)

        def layer(i, carry):
            tails, acc = carry
            if args.form == "gathered":
                out, *tails = qa.quantized_fused_decode_attention(
                    q, new, new, *pool, *tails, i, jnp.int32(3),
                    lens, vlen, lens + 3, sliding_window=window,
                )
            elif args.form in ("latent", "latent32"):
                out, *tails = pa.quantized_latent_paged_fused_attention(
                    q, new, *pool, *tails, i, jnp.int32(3), table,
                    lens, vlen, lens + 3, scale=192 ** -0.5, **walk,
                )
            elif args.form == "latent-grid":
                out = pa.quantized_latent_paged_attention(
                    q, *(jax.lax.dynamic_index_in_dim(p, i, keepdims=False)
                         for p in pool),
                    table, jnp.where(vlen > 0, lens, 0), scale=192 ** -0.5,
                )
            else:
                out, *tails = pa.quantized_paged_fused_attention(
                    q, new, new, *pool, *tails, i, jnp.int32(3), table,
                    lens, vlen, lens + 3, sliding_window=window,
                    **({} if select is None else {"select": select}),
                )
            return tuple(tails), acc + out.astype(jnp.float32)

        return jax.lax.fori_loop(
            0, LAYERS, layer, (tails, jnp.zeros(q.shape, jnp.float32))
        )

    rng = np.random.default_rng(args.seed)
    lens = row_lengths(kind, b, t, rng)
    hi = -(-lens // PS)
    # the pages the sweep fetches: under a window, from the first that holds
    # a position the query (3 past the pool's end) still sees
    lo = np.minimum(np.maximum(lens + 3 - window + 1, 0) // PS, hi) if window else 0 * hi
    live = hi - lo
    if live.sum() >= pages:
        sys.exit(f"{kind}: {live.sum()} live pages, the pool has {pages}")
    ids = rng.permutation(pages - 1)[: live.sum()] + 1
    table = np.zeros((b, t), np.int32)
    at = 0
    for r in range(b):
        table[r, lo[r] : hi[r]] = ids[at : at + live[r]]
        at += live[r]
    vlen = np.where(lens > 0, 4, 0).astype(np.int32)
    select = None
    if "select" in case:  # positive where attended: topk a row, at random
        chosen = np.zeros((b, t * PS), np.float32)
        for r in range(b):
            chosen[r, rng.permutation(lens[r])[: case["select"]]] = 1.0
        select = (jnp.asarray(chosen.reshape(b, t, 1, PS)),
                  jnp.ones((b, 1, KT), jnp.float32))
    tails = (
        jnp.zeros((LAYERS, b, HKV, KT, D), jnp.int8),
        jnp.zeros((LAYERS, b, HKV, KT), jnp.float32),
    ) * (1 if latent else 2)
    big = pool
    if args.form == "gathered":
        big = jax.block_until_ready(gather(pool, jnp.asarray(table)))
    # once a window: the scale planes in the form the kernel copies by the
    # page, where this checkout has one for the pool's shape
    join = jax.jit(getattr(pa, "joined_scale_rows", lambda ks, vs: None))
    joined = None if args.form != "inplace" else join(pool[1], pool[3])
    if joined is not None:
        big = (pool[0], jax.block_until_ready(joined), pool[2], None)
    argv = (big, tails, jnp.asarray(table), jnp.asarray(lens),
            jnp.asarray(vlen), select)
    t0 = time.perf_counter()
    tails, acc = step(*argv)
    jax.block_until_ready(acc)
    first_call_s = time.perf_counter() - t0  # trace, compile, run
    def busy_ms(prepare):  # a traced call of what a window prepares once
        with tempfile.TemporaryDirectory() as td:
            with jax.profiler.trace(td):
                jax.block_until_ready(prepare())
            found = aggregate(find_xplane(td))["devices"]
        return found[0]["busy_ns"] / 1e6 if found else 0.0

    gather_ms = join_ms = 0.0
    if args.form == "gathered":
        gather_ms = busy_ms(lambda: gather(pool, argv[2]))
    if joined is not None:
        join_ms = busy_ms(lambda: join(pool[1], pool[3]))
    with tempfile.TemporaryDirectory() as td:
        with jax.profiler.trace(td):
            for _ in range(args.reps):
                tails, acc = step(big, tails, *argv[2:])
            jax.block_until_ready(acc)
        agg = aggregate(find_xplane(td))
    ns = sum(v for k, v in agg["ops_ns"].items() if kernel in k)
    calls = sum(v for k, v in agg["op_counts"].items() if kernel in k)
    busy = agg["devices"][0]["busy_ns"] if agg["devices"] else 0
    beside = sorted(
        ((v, k) for k, v in agg["ops_ns"].items() if kernel not in k),
        reverse=True,
    )[:4]
    planes = 1 if latent else 2
    # the grid steps of a pipelined-block sweep: what the ``(rows, table
    # blocks)`` grid held, and what the walk of PR 48 keeps of it (a row's
    # blocks with a live page, one for a row with none)
    n = pa._pages_per_block(t, HKV, PS, D, KT, planes)
    walked = int(np.maximum(-(-hi // n) - lo // n, 1).sum())
    print(json.dumps({
        "form": args.form, "gather_ms_a_window": round(gather_ms, 3),
        "join_ms_a_window": round(join_ms, 3),
        "occupancy": kind, "rows": b, "width": t,
        "pages_a_block": pa._pages_per_block(t, HKV, PS, D, KT, planes),
        "pages_a_block_own": pick(t, HKV, PS, D, KT, planes),
        "live_positions_pct": round(100 * lens.sum() / (b * t * PS), 1),
        "live_pages": int(live.sum()), "kernel_calls": calls,
        "kernel_us_a_call": round(ns / max(calls, 1) / 1e3, 2),
        "kernel_ms_a_step": round(ns / args.reps / 1e6, 3),
        "kernel_us_a_live_page": round(
            ns / args.reps / LAYERS / max(int(live.sum()), 1) / 1e3, 4
        ),
        "steps_walked": walked, "steps_grid": b * -(-t // n),
        "kernel_us_a_walked_step": round(
            ns / args.reps / LAYERS / walked / 1e3, 4
        ),
        "busy_ms_a_step": round(busy / args.reps / 1e6, 3),
        "beside_kernel_ms_a_step": round((busy - ns) / args.reps / 1e6, 3),
        "beside_kernel_top": {
            k: round(v / args.reps / 1e6, 3) for v, k in beside
        },
        "first_call_s": round(first_call_s, 2),
        "checksum": float(jnp.sum(acc)),
        # of the step's results and its tail, bit for bit
        "sha": hashlib.sha256(b"".join(
            np.asarray(x).tobytes() for x in (acc, *tails)
        )).hexdigest()[:16],
        "device": jax.devices()[0].device_kind,
    }), flush=True)


if __name__ == "__main__":
    main()
