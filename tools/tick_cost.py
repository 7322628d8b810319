"""What the engine's tracing costs a tick, on this machine's host.

    python tools/tick_cost.py [ticks]

Four readings, each the median of five rounds of ``ticks`` calls:

* ``step()`` of an engine whose slots are all idle (nothing queued, nothing
  decoding: the tick is its bookkeeping alone) with ``trace_cfg=None``; with
  ``TraceConfig()`` and nobody reading the ticks (the dispatch clock unarmed:
  one tick's ``begin`` / ``end``, its ``engine_tick`` step annotation, one
  ``admit`` region, the tick record and the seven counters); and with the
  clock armed (a lease held: the phase marks and ``engine_clocked_ticks``
  besides);
* the recorder's calls of a FULL tick alone, armed (``begin``, the four
  regions a decoding tick opens, the dispatch clock's ``enter`` / ``leave`` /
  ``settle`` of its one decode dispatch with the watcher thread running
  beside it, ``end`` with every field), since a tick that decodes is bound by
  its device and hides a few microseconds; and the same unarmed, so that the
  difference is what an armed clock adds a tick.

The model is tiny: this times the host, and says nothing of a device.
"""
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from distributed_llm_inference_tpu.config import (  # noqa: E402
    CacheConfig, EngineConfig, ModelConfig, TraceConfig,
)
from distributed_llm_inference_tpu.engine.engine import InferenceEngine  # noqa: E402
from distributed_llm_inference_tpu.models import llama  # noqa: E402
from distributed_llm_inference_tpu.utils.metrics import Metrics  # noqa: E402
from distributed_llm_inference_tpu.utils.tracing import FlightRecorder  # noqa: E402


def engine(trace_cfg):
    cfg = ModelConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=2,
        num_heads=2, num_kv_heads=2, head_dim=16,
    )
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return InferenceEngine(
        cfg, params,
        EngineConfig(max_batch_size=32, prefill_buckets=(8,), max_seq_len=64,
                     dtype="float32"),
        CacheConfig(kind="paged", page_size=8, num_pages=64,
                    max_pages_per_session=8),
        trace_cfg=trace_cfg,
    )


def median_us(fn, ticks, rounds=5):
    fn()
    out = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(ticks):
            fn()
        out.append((time.perf_counter() - t0) / ticks * 1e6)
    return statistics.median(out)


#: a dispatch's result, ready: the watcher thread wakes for it as it does
#: for a pipelined tick's tokens
READY = jnp.zeros((), jnp.int32)


def full_tick(fr):
    fr.begin()
    clocked = fr.clock.armed
    with fr.region("admit"):
        pass
    with fr.region("dispatch"):
        if clocked:
            entry = fr.clock.enter()
            fr.clock.leave(entry, READY, "decode", 16)
        with fr.region("blocked"):
            pass
        if clocked:
            fr.clock.settle(entry)
        with fr.region("deliver"):
            pass
    fr.end(kind="plain", occupancy=32, queued=0, admitted=0, chunking=0,
           parked=0, overlap_inflight=0, pending=False, events=32,
           dispatch=("decode", (32, 1, 38), 12345),
           dispatches=[("decode", (32, 1, 38), 12345)], free_pages=100)


def main(argv):
    ticks = int(argv[0]) if argv else 5000
    off, on = engine(None), engine(TraceConfig())
    disabled = median_us(off.step, ticks)
    unarmed = median_us(on.step, ticks)
    on.flight.clock.lease(3600.0)
    armed = median_us(on.step, ticks)
    fr = FlightRecorder(512, Metrics())
    recorder_unarmed = median_us(lambda: full_tick(fr), ticks)
    fr.clock.lease(3600.0)
    recorder_armed = median_us(lambda: full_tick(fr), ticks)
    on.flight.clock.stop()
    fr.clock.stop()
    print({
        "platform": jax.devices()[0].platform, "ticks": ticks,
        "idle_step_us_disabled": round(disabled, 2),
        "idle_step_us_unarmed": round(unarmed, 2),
        "idle_step_us_armed": round(armed, 2),
        "full_tick_recorder_calls_us_unarmed": round(recorder_unarmed, 2),
        "full_tick_recorder_calls_us_armed": round(recorder_armed, 2),
        "full_tick_armed_clock_us": round(recorder_armed - recorder_unarmed, 2),
    })


if __name__ == "__main__":
    main(sys.argv[1:])
