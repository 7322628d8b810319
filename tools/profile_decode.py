"""Profile the fused-decode step on the TPU and attribute device time by op.

Usage: python tools/profile_decode.py [phase] [batch] [ctx]
  phase in {int8_kvq, int4_kvq, bf16, int8} (dense-cache phases).

Reuses bench.py's param builders and decode driver, wraps the timed loop in a
jax.profiler trace, and prints the per-op aggregate via utils/xplane.
"""
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp

import bench
from distributed_llm_inference_tpu.cache.dense import (
    DenseKVCache,
    QuantizedDenseKVCache,
)
from distributed_llm_inference_tpu.models import llama
from distributed_llm_inference_tpu.utils.xplane import aggregate, find_xplane
from xplane_profile import report  # the script's own directory


def main():
    phase = sys.argv[1] if len(sys.argv) > 1 else "int8_kvq"
    batch = int(sys.argv[2]) if len(sys.argv) > 2 else 112
    ctx = int(sys.argv[3]) if len(sys.argv) > 3 else 256
    scan_k = int(sys.argv[4]) if len(sys.argv) > 4 else 16
    build, _, cache_cls = bench.PHASES[phase]
    use_kernel = cache_cls == "dense_kernel"
    if use_kernel:
        cache_cls = QuantizedDenseKVCache
    cfg = bench.LLAMA2_7B
    params = build(cfg, jnp.bfloat16)
    jax.block_until_ready(params)

    writes = 2 * scan_k
    buf = min(ctx, ctx // 2 + writes)
    cache = cache_cls.create(
        cfg.num_layers, batch, buf, cfg.num_kv_heads, cfg.head_dim,
        jnp.bfloat16, **({"use_kernel": True} if use_kernel else {}),
    )
    cache = cache.replace(lengths=jnp.full((batch,), ctx // 2, jnp.int32))
    active = jnp.ones((batch,), bool)

    def decode(params, tokens, cache):
        def step_fn(i, logits, alive):
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            return nxt, alive.astype(jnp.int32), alive, nxt

        emits, cache = llama.multi_decode_apply(
            cfg, params, tokens, cache, scan_k, step_fn, active,
            active.astype(jnp.int32),
        )
        return emits[-1][:, None], cache

    decode = jax.jit(decode, donate_argnums=(2,))
    tokens = jnp.zeros((batch, 1), jnp.int32)
    tokens, cache = decode(params, tokens, cache)
    jax.block_until_ready(tokens)
    cache = cache.replace(lengths=jnp.full((batch,), ctx // 2, jnp.int32))

    reps = 2
    with tempfile.TemporaryDirectory() as td:
        t0 = time.perf_counter()
        with jax.profiler.trace(td):
            for _ in range(reps):
                tokens, cache = decode(params, tokens, cache)
            jax.block_until_ready(tokens)
        dt = time.perf_counter() - t0
        agg = aggregate(find_xplane(td))
    per_step = dt / reps * 1e3
    print(f"wall {per_step:.2f} ms/call ({scan_k} tokens) -> "
          f"{batch*scan_k*reps/dt:.0f} tok/s")
    print("\n".join(report(agg)))


if __name__ == "__main__":
    main()
