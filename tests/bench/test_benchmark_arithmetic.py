"""The generator's and the metric readers' arithmetic, on made-up records:
no server, no JAX. What is pinned here is what the numbers in the ledger
mean (ISSUE 22): timing from the due time, nearest-rank percentiles,
tokens counted only inside the window, a failed request missing every
limit."""

import importlib
import json
import math
import os
import types

import pytest

from benchmark import peaks, samples, stats
from benchmark.generators import closed_loop, open_poisson
from benchmark.loadgen import Record, draw_lengths, seeded_prompt

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(os.path.dirname(HERE)), "benchmark")


def traffic(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def record(i, due=None, sent=None, arrivals=(), asked=None, status=200,
           phase="traffic", prompt=10, error=None):
    asked = len(arrivals) if asked is None else asked
    r = Record(index=i, phase=phase, prompt_len=prompt, max_tokens=asked,
               due=due, sent=sent, status=status, error=error)
    r.arrivals = list(arrivals)
    r.tokens = [5] * len(arrivals)
    r.ended = (arrivals[-1] if arrivals else (sent or due or 0.0)) + 0.001
    return r


def run_of(records, loop="open", t0=100.0, seconds=10.0, **kw):
    return types.SimpleNamespace(
        records=records, loop=loop, t0=t0, seconds=seconds,
        shapes={"vocab_size": 100, "max_batch_size": 4, "decode_steps": 16},
        traffic={"slo": {"ttft_ms": 2000, "tpot_ms": 100}}, **kw,
    )


@pytest.mark.parametrize("q,want", [(50, 3), (90, 5), (100, 5), (1, 1), (20, 1), (21, 2)])
def test_percentile_is_nearest_rank(q, want):
    assert stats.percentile([5, 1, 4, 2, 3], q) == want


def test_percentile_of_nothing_is_nothing():
    assert stats.percentile([], 50) is None and stats.median([]) is None


def test_a_miss_sorts_after_every_reading():
    assert stats.percentile([0.1, 0.2, stats.MISSED], 100) == stats.MISSED
    assert stats.percentile([0.1, 0.2, stats.MISSED], 50) == 0.2


def mixes_the_cells_name():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        cells = json.load(f)["workloads"]
    return list(dict.fromkeys(w["traffic"] for w in cells))


@pytest.mark.parametrize("mix", mixes_the_cells_name())
def test_same_seed_same_plan_other_seed_other_plan(mix):
    params = traffic(mix)
    gen = importlib.import_module(f"benchmark.generators.{params['generator']}")
    a, b, c = (gen.plan(params, s, 45.0) for s in (7, 7, 8))
    assert a == b and a != c


def test_open_schedule_has_the_rate_and_the_lead_in():
    params = {**traffic("chat-open"), "rate_rps": 5.0, "lead_in_s": 4}
    plan = open_poisson.plan(params, 3, 200.0)
    due = [r["due"] for r in plan]
    assert due == sorted(due) and -4 <= due[0] < 0 and due[-1] < 200
    # a Poisson process conditioned on its count: every seed offers the
    # same number of requests, at other instants
    assert sum(1 for d in due if d >= 0) == 1000 and len(due) == 1020
    other = open_poisson.plan(params, 4, 200.0)
    assert len(other) == 1020 and [r["due"] for r in other] != due
    assert sorted(r["prompt_len"] for r in other) == sorted(r["prompt_len"] for r in plan)
    assert all(16 <= r["prompt_len"] <= 2000 and 8 <= r["max_tokens"] <= 512 for r in plan)
    gaps = [b - a for a, b in zip(due, due[1:])]
    mean = sum(gaps) / len(gaps)
    cv = (sum((g - mean) ** 2 for g in gaps) / len(gaps)) ** 0.5 / mean
    assert 0.85 < cv < 1.15          # exponential gaps, not a metronome


def test_closed_plan_shared_set_and_staggered_first_wave():
    rag = closed_loop.plan(traffic("rag-replay"), 1, 45.0)
    assert list(rag["streams"]) == ["shared"] and len(rag["streams"]["shared"]) == 24
    assert rag["first_wave_share"] == [1.0] * 4
    reason = closed_loop.plan(traffic("reason-closed"), 1, 45.0)
    assert len(reason["streams"]) == 32
    asked = sorted(r["max_tokens"] for s in reason["streams"].values() for r in s)
    assert asked[0] < 1030 and asked[-1] > 2040 and len(asked) == 256   # stratified
    assert all(0 < s <= 1 for s in reason["first_wave_share"])
    assert len(set(reason["first_wave_share"])) == 32


def test_lengths_are_stratified_clipped_and_prompts_seeded():
    import numpy as np

    spec = {"dist": "lognormal", "median": 100, "sigma": 2.0, "min": 50, "max": 150}
    drawn = draw_lengths(np.random.default_rng(0), spec, 200)
    again = draw_lengths(np.random.default_rng(1), spec, 200)
    assert min(drawn) == 50 and max(drawn) == 150
    assert sorted(drawn) == sorted(again) and drawn != again    # order only
    plain = {"dist": "lognormal", "median": 100, "sigma": 0.5, "min": 1, "max": 10**6}
    values = sorted(draw_lengths(np.random.default_rng(0), plain, 101))
    assert values[50] == 100 and 2.5 < values[-1] / values[50] < 4.5
    uniform = draw_lengths(np.random.default_rng(0), {"dist": "uniform", "min": 10, "max": 20}, 11)
    assert sorted(uniform)[0] >= 10 and sorted(uniform)[-1] <= 20 and len(set(uniform)) >= 10
    assert draw_lengths(None, {"dist": "fixed", "value": 9}, 3) == [9, 9, 9]
    with pytest.raises(ValueError):
        draw_lengths(np.random.default_rng(0), {"dist": "zipf"}, 2)
    assert seeded_prompt(1, 2, 30, 100) == seeded_prompt(1, 2, 30, 100)
    assert seeded_prompt(1, 2, 30, 100) != seeded_prompt(1, 3, 30, 100)
    assert all(1 <= t < 100 for t in seeded_prompt(1, 2, 30, 100))


def test_open_loop_ttft_runs_from_the_due_time_and_lateness_is_reported():
    # due at 101.0, the generator got to it 0.5 s late, first token at 102.0
    r = record(1, due=101.0, sent=101.5, arrivals=[102.0, 102.1])
    run = run_of([r])
    assert samples.ttft_s(run) == [pytest.approx(1.0)]
    assert samples.lateness_s(run) == [pytest.approx(0.5)]
    late = importlib.import_module("benchmark.layer_metrics.generator_late_ms_p90")
    assert late.read(run) == pytest.approx(500.0)


def test_closed_loop_ttft_runs_from_the_send():
    r = record(1, sent=101.5, arrivals=[102.0, 102.1])
    assert samples.ttft_s(run_of([r], loop="closed")) == [pytest.approx(0.5)]


def test_only_requests_due_inside_the_window_are_timed():
    before = record(1, due=99.0, sent=99.0, arrivals=[100.5])
    inside = record(2, due=100.0, sent=100.0, arrivals=[100.2])
    after = record(3, due=110.0, sent=110.0, arrivals=[110.2])
    warm = record(4, sent=105.0, arrivals=[105.2], phase="warm")
    run = run_of([before, inside, after, warm])
    assert samples.timed_requests(run) == [inside]


def test_tokens_outside_the_window_are_not_counted():
    r = record(1, due=99.0, sent=99.0, arrivals=[99.5, 100.0, 105.0, 109.999, 110.0, 111.0])
    run = run_of([r])
    assert samples.tokens_in_window(run) == 3
    # out_tok_s tapers the edges over a ninth of the window each: of the
    # three, only the token in the middle has its whole weight
    out = importlib.import_module("benchmark.end_to_end.out_tok_s")
    edge = 10.0 / 9.0
    near_end = 0.5 - 0.5 * math.cos(math.pi * 0.001 / edge)
    assert out.read(run) == pytest.approx((1.0 + near_end) / (10.0 - edge))


@pytest.mark.parametrize("phase", [0.0, 0.05, 0.11, 0.2, 0.29, 0.336])
def test_out_tok_s_does_not_step_with_the_phase_of_the_bursts(phase):
    # four rows, 16 tokens a row every 0.337 s: with hard edges a 45 s window
    # holds 133 or 134 bursts by the phase (0.75%); tapered, the same rate
    period, seconds = 0.337, 45.0
    bursts = [100.0 + phase + k * period for k in range(-3, 140)]
    rows = [
        record(i, sent=90.0, arrivals=[t for t in bursts for _ in range(16)])
        for i in range(4)
    ]
    run = run_of(rows, loop="closed", seconds=seconds)
    out = importlib.import_module("benchmark.end_to_end.out_tok_s")
    assert out.read(run) == pytest.approx(64 / period, rel=1e-4)


def test_tpot_needs_32_tokens_a_good_end_and_an_end_inside_the_window():
    long_ok = record(1, due=100.0, sent=100.0, arrivals=[101.0 + 0.01 * i for i in range(41)])
    short = record(2, due=100.0, sent=100.0, arrivals=[101.0 + 0.5 * i for i in range(5)])
    cut = record(3, due=100.0, sent=100.0, arrivals=[101.0 + 0.02 * i for i in range(40)], asked=50)
    late = record(4, due=109.0, sent=109.0, arrivals=[109.5 + 0.03 * i for i in range(40)])
    run = run_of([long_ok, short, cut, late])
    assert samples.tpot_s(run) == [pytest.approx(0.01)]
    tpot = importlib.import_module("benchmark.end_to_end.tpot_ms_p50")
    assert tpot.read(run) == pytest.approx(10.0)


def test_a_failed_or_refused_request_misses_every_limit():
    good = record(1, due=100.0, sent=100.0, arrivals=[100.5 + 0.01 * i for i in range(40)])
    slow_first = record(2, due=101.0, sent=101.0, arrivals=[104.0 + 0.01 * i for i in range(40)])
    slow_pace = record(3, due=102.0, sent=102.0, arrivals=[102.5 + 0.2 * i for i in range(40)])
    refused = record(4, due=103.0, sent=103.0, status=429, asked=8, error="full")
    short = record(5, due=104.0, sent=104.0, arrivals=[104.1, 104.2], asked=8)
    run = run_of([good, slow_first, slow_pace, refused, short])
    slo = importlib.import_module("benchmark.layer_metrics.slo_ok_pct")
    assert slo.read(run) == pytest.approx(20.0)
    assert samples.ttft_s(run)[3] == stats.MISSED
    p90 = importlib.import_module("benchmark.end_to_end.ttft_ms_p90")
    # nearest rank 5 of 5 is the miss: reported as longer than any reading
    assert p90.read(run) > 3000.0
    assert not refused.ok(100) and not short.ok(100) and good.ok(100)


def test_a_token_out_of_range_is_not_ok():
    r = record(1, due=100.0, sent=100.0, arrivals=[100.5, 100.6])
    r.tokens = [5, 100]
    assert not r.ok(100)


def test_peaks_are_keyed_by_device_kind_and_an_unknown_kind_raises():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e == peaks.peaks_for("TPU v5e")
    assert v5e["bf16_flops"] == 197e12 and v5e["int8_ops"] == 393e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["hbm_bytes"] == 16e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("cpu")


def test_flops_count_top_k_experts_and_valid_tokens_only():
    from benchmark import flops

    dense, moe = config("mistral-7b"), config("mixtral-8x7b-8l")
    # Mistral-7B: 7.24e9 parameters, 0.13e9 of them the embedding lookup
    assert flops.matmul_params_per_token(dense) == pytest.approx(7.11e9, rel=0.01)
    layer = 4096 * (4096 + 1024 + 1024 + 4096) + 2 * 3 * 4096 * 14336 + 4096 * 8
    assert flops.matmul_params_per_token(moe) == 8 * layer + 4096 * 32000
    # a decode step reads all 8 experts, a token is multiplied with 2
    assert flops.stored_weight_bytes(moe, 1.0) > 3.5 * flops.matmul_params_per_token(moe)
    assert flops.prompt_flops(dense, 2048) == pytest.approx(2 * 7.11e9 * 2048, rel=0.08)
    assert flops.decode_token_flops(dense, 8000) == flops.decode_token_flops(dense, 4096)
    assert flops.kv_bytes_per_token(dense, 1.0) == 2 * 32 * 8 * 128


def test_the_paged_decode_kernels_bytes_and_operations_come_from_shapes():
    from benchmark import flops
    from benchmark.kernels import quantized_paged_fused_attention as kernel

    dense = config("mistral-7b")
    # one position of one layer: int8 K and V of 8 heads of 128, and a
    # float32 scale a head for each
    assert kernel.bytes_read(dense, 1) == 2 * 8 * 128 + 2 * 8 * 4 == 2112
    # a call a layer: 32 of them read what ``flops.py`` counts for a token
    assert 32 * kernel.bytes_read(dense, 64) == 64 * flops.kv_bytes_per_token(dense, 1 + 4 / 128)
    assert kernel.operations(dense, 1) == 4 * 32 * 128
    # a live page: 0.165 us of bytes at 819 GB/s, 30 times its operations'
    v5e = peaks.peaks_for("TPU v5 lite")
    page_s = kernel.bytes_read(dense, 64) / v5e["hbm_bytes_per_s"]
    assert page_s == pytest.approx(0.165e-6, rel=0.01)
    assert 25 < page_s / (kernel.operations(dense, 64) / v5e["bf16_flops"]) < 35


def test_the_decode_kernels_roofline_share_is_taken_over_the_traced_span_alone():
    reader = importlib.import_module("benchmark.layer_metrics.paged_decode_attn_roofline_pct")
    dense = config("mistral-7b")
    decode = lambda live, steps=16: ["decode", [32, steps, 38], live]   # noqa: E731
    ticks = {
        1: {"t0_ns": 999.5e9, "dispatches": [decode(90000)]},     # before it
        2: {"t0_ns": 1000.5e9, "dispatches": [["prefill", [1, 2048], 100], decode(20000)]},
        3: {"t0_ns": 1001.5e9, "dispatches": [decode(22000)]},
        4: {"t0_ns": 1002.5e9, "dispatches": [decode(90000)]},    # after it
        5: {"t0_ns": 1001.7e9, "dispatches": []},                 # an idle tick
    }

    def run(ticks=ticks, **closed):
        return types.SimpleNamespace(
            closed=closed, ticks=ticks, conf=dense, device={"kind": "TPU v5 lite"},
        )

    def traced(calls, positions, share=0.3):
        least_s = calls * positions * 2112 / 819e9
        return {"trace_epoch_s": [1000.0, 1002.0], "trace": {"kernels_device0": {
            "quantized_paged_fused_attention": {"count": calls, "sum_s": least_s / share},
            "quantized_ragged_paged_attention": {"count": 64, "sum_s": 0.3},
        }}}

    # two dispatches of 16 steps over 32 layers; a mean of 21000 live
    # positions a call; the kernel took 3.3 times what their bytes take
    whole = traced(2 * 16 * 32, 21000)
    assert reader.read(run(**whole)) == pytest.approx(30.0)
    assert reader.LAYER == "kernels" and reader.DEVICE_METRIC
    # the device lags the host by a tick: a dispatch more or less in the
    # trace than in the records moves the count, not the mean a call
    assert reader.read(run(**traced(3 * 16 * 32, 21000))) == pytest.approx(30.0)
    # a dispatch of 4 steps is 4 calls a layer, one of 16 is 16: the mean a
    # call is (16 * 20000 + 4 * 30000) / 20, not 25000
    mixed = {2: ticks[2], 3: {"t0_ns": 1001.5e9, "dispatches": [decode(30000, 4)]}}
    assert reader.read(run(mixed, **traced(20 * 32, 22000))) == pytest.approx(30.0)
    # records of seven dispatches against the events of two: these decode
    # dispatches did not run this kernel, and their mean is no call's
    other = {i: {"t0_ns": (1000.1 + i / 10) * 1e9, "dispatches": [decode(20000)]}
             for i in range(7)}
    assert reader.read(run(other, **whole)) is None
    # nothing to read: no trace, no such kernel in it, no span, no tick in it
    assert reader.read(run()) is None
    assert reader.read(run(trace={"kernels_device0": {}}, trace_epoch_s=[1000.0, 1002.0])) is None
    assert reader.read(run(trace=whole["trace"])) is None
    assert reader.read(run(trace=whole["trace"], trace_epoch_s=[10.0, 12.0])) is None


def test_the_traffic_files_schedule_seed_makes_the_plan_and_the_run_seed_the_tokens():
    import asyncio

    from benchmark import run as run_mod

    seen = []

    class Generator:
        @staticmethod
        async def drive(ctx, params, seed, seconds):
            seen.append(seed)

    class Ctx:
        seconds = 1.0

        async def finish(self):
            pass

    asyncio.run(run_mod.one_window(Ctx(), Generator, {"schedule_seed": 77}, 5))
    asyncio.run(run_mod.one_window(Ctx(), Generator, {}, 5))
    assert seen == [77, 5]
    for mix in mixes_the_cells_name():
        assert isinstance(traffic(mix)["schedule_seed"], int)


def test_gateway_overhead_compares_the_sessions_that_finished_in_the_window():
    done = record(1, sent=99.0, arrivals=[99.5, 104.0])            # ended inside
    running = record(2, sent=105.0, arrivals=[108.0, 120.0])       # ended after
    run = run_of([done, running], loop="closed",
                 closed={"engine_ttft_s": [0.45]})
    reader = importlib.import_module("benchmark.layer_metrics.gateway_overhead_ms_p50")
    assert reader.read(run) == pytest.approx(50.0)
    run.closed = {"engine_ttft_s": []}
    assert reader.read(run) is None
