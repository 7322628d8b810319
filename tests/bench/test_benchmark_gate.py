"""What a judged percentile may rest on, and what a traced run may print
(ISSUE 37), on made-up records and a stub child: no server, no JAX.

* every end-to-end percentile that lists a cell has at least ten timed
  requests beyond its rank in that cell, counted from the traffic file's
  plan: ``ttft_ms_p90`` over 45 requests was the 41st with four beyond it,
  and ONE request that caught another tick moved it by 55 ms of 693;
* the statistic itself on hand-made lists;
* the readers that share its arithmetic where they are not judged
  (``admission_ttft_ms_p50``, ``slo_ok_pct``) read what they read;
* a ``--trace 1`` run whose trace is empty takes it once more inside the
  window, and one with no trace at all raises instead of printing a line
  without ``busy_s`` and ``window_s``.
"""

import asyncio
import importlib
import json
import os
import time
import types

import pytest

from benchmark import run as bench_run
from benchmark import samples, stats
from benchmark.loadgen import Record
from benchmark.reduce import xplane

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def traffic_of(cell):
    with open(os.path.join(REPO, "benchmark", "traffic", cell["traffic"] + ".json")) as f:
        return json.load(f)


def planned_timed_requests(params: dict, seconds: float) -> int:
    """Timed requests of one window, from the traffic file alone. Open loop:
    the plan's requests due inside the window. Closed loop: how many its
    fixed schedule sends in a window is the system's doing, so a file whose
    cell is judged by a percentile states it (``timed_requests``, from the
    runs that set the bound)."""
    gen = importlib.import_module(f"benchmark.generators.{params['generator']}")
    if gen.LOOP == "open":
        plan = gen.plan(params, params.get("schedule_seed", 0), seconds)
        return sum(1 for r in plan if 0 <= r["due"] < seconds)
    assert "timed_requests" in params, (
        "a closed loop judged by a percentile states the requests its "
        "schedule times in a window"
    )
    return int(params["timed_requests"])


def judged_percentiles():
    """``(metric, cell)`` for every end-to-end metric that lists its cells
    and whose reader says which percentile it is (``PERCENTILE``)."""
    out = []
    for m in bench()["end_to_end"]:
        reader = importlib.import_module(f"benchmark.end_to_end.{m['name']}")
        if getattr(reader, "PERCENTILE", None) is not None:
            out.extend((m["name"], cell) for cell in m.get("workloads", ()))
    return out


def test_the_ttft_percentiles_are_among_the_judged():
    assert ("ttft_ms_p50", "mistral-7b.chat") in judged_percentiles()


@pytest.mark.parametrize("metric,cell", judged_percentiles())
def test_a_judged_percentile_has_ten_timed_requests_beyond_its_rank(metric, cell):
    b = bench()
    reader = importlib.import_module(f"benchmark.end_to_end.{metric}")
    params = traffic_of(next(w for w in b["workloads"] if w["name"] == cell))
    n = planned_timed_requests(params, b["run_seconds"])
    assert stats.beyond_rank(n, reader.PERCENTILE) >= stats.MIN_BEYOND, (metric, cell, n)
    # and as many on the near side: a median of 12 would pass the line above
    assert stats.rank_of(n, reader.PERCENTILE) > stats.MIN_BEYOND, (metric, cell, n)


def test_a_percentile_name_says_its_rank():
    for m in bench()["end_to_end"]:
        reader = importlib.import_module(f"benchmark.end_to_end.{m['name']}")
        q = getattr(reader, "PERCENTILE", None)
        if q is not None:
            assert m["name"].endswith(f"_p{q:g}"), m["name"]


@pytest.mark.parametrize("n,q,rank,beyond", [
    (45, 90, 41, 4), (45, 75, 34, 11), (135, 90, 122, 13), (100, 90, 90, 10),
    (99, 90, 90, 9), (135, 50, 68, 67), (1, 50, 1, 0),
])
def test_rank_and_what_lies_beyond_it(n, q, rank, beyond):
    assert stats.rank_of(n, q) == rank and stats.beyond_rank(n, q) == beyond
    assert stats.percentile(list(range(1, n + 1)), q) == rank


def record(i, due, first=None, tokens=40, pace=0.02, ok=True):
    r = Record(index=i, phase="traffic", prompt_len=10, max_tokens=tokens,
               due=due, sent=due + 0.001, status=200 if ok else 503)
    if first is not None:
        r.arrivals = [due + first + pace * k for k in range(tokens)]
        r.tokens = [5] * tokens
    r.ended = (r.arrivals[-1] if r.arrivals else due) + 0.001
    if not ok:
        r.error = "refused"
    return r


def run_of(records, loop="open", t0=100.0, seconds=50.0):
    return types.SimpleNamespace(
        records=records, loop=loop, t0=t0, seconds=seconds,
        shapes={"vocab_size": 100}, traffic={"slo": {"ttft_ms": 2000, "tpot_ms": 100}},
    )


def ttfts(values):
    """One timed request a reading (seconds; ``None`` is a request that got
    no token), due a tenth of a second apart."""
    return run_of([
        record(i, 100.0 + 0.1 * i, first=v, ok=v is not None)
        for i, v in enumerate(values)
    ])


def test_ties_at_the_rank_read_the_tied_value():
    run = ttfts([0.5] * 5 + [0.7] * 10 + [0.9] * 5)
    assert samples.ttft_percentile_ms(run, 50.0) == pytest.approx(700.0)
    assert samples.ttft_percentile_ms(run, 75.0) == pytest.approx(700.0)
    assert samples.ttft_percentile_ms(run, 90.0) == pytest.approx(900.0)


def test_a_miss_beyond_the_rank_leaves_the_reading_and_one_inside_it_does_not():
    readings = [0.1 * k for k in range(1, 21)]            # 0.1 .. 2.0 s
    whole = samples.ttft_percentile_ms(ttfts(readings), 90.0)
    assert whole == pytest.approx(1800.0)                 # the 18th of 20
    # the slowest request fails instead: still beyond the rank
    beyond = samples.ttft_percentile_ms(ttfts(readings[:-1] + [None]), 90.0)
    assert beyond == pytest.approx(1800.0)
    # a fast one fails: it now sorts after every reading, and the 18th is
    # what the 19th was
    inside = samples.ttft_percentile_ms(ttfts([None] + readings[1:]), 90.0)
    assert inside == pytest.approx(1900.0)
    # three fail: the rank falls on a miss, which reads as how long the run
    # watched it, longer than any real reading
    missed = samples.ttft_percentile_ms(ttfts([None] * 3 + readings[3:]), 90.0)
    assert missed > 2000.0


def test_one_request_that_changes_sides_moves_a_rank_with_ten_beyond_it_little():
    """The mechanism of PR 36's 19 runs, in small: one request's first token
    comes a tick (0.37 s) sooner. With four beyond the rank and neighbours
    55 ms apart the reading falls by the whole gap; with thirteen beyond it
    and neighbours a few ms apart it moves by one neighbour."""
    sparse = [0.3 + 0.008 * k for k in range(39)] + [0.637, 0.692, 0.709, 0.731, 0.745, 0.776]
    flipped = sparse[:-1] + [0.776 - 0.366]
    assert len(sparse) == 45
    fall = samples.ttft_percentile_ms(ttfts(sparse), 90.0) - samples.ttft_percentile_ms(ttfts(flipped), 90.0)
    assert fall == pytest.approx(55.0)
    dense = [0.3 + 0.005 * k for k in range(135)]
    flipped = dense[:-1] + [dense[-1] - 0.366]
    fall = samples.ttft_percentile_ms(ttfts(dense), 90.0) - samples.ttft_percentile_ms(ttfts(flipped), 90.0)
    assert fall == pytest.approx(5.0)


#: twelve timed requests as a run might read them: TTFT seconds and the pace
#: of the tokens after it; one refused. What the two readers below said of
#: this list before ISSUE 37 changed the arithmetic beside them.
PINNED = [
    (0.41, 0.02), (0.45, 0.02), (0.52, 0.03), (0.58, 0.02), (0.63, 0.02),
    (0.66, 0.11), (0.71, 0.02), (0.93, 0.02), (1.46, 0.02), (1.98, 0.02),
    (2.05, 0.02), (None, 0.02),
]


def pinned_run(loop):
    return run_of([
        record(i, 101.0 + i, first=first, pace=pace, ok=first is not None)
        for i, (first, pace) in enumerate(PINNED)
    ], loop=loop)


def test_admission_ttft_and_slo_ok_read_what_they_read_before():
    from benchmark.layer_metrics import admission_ttft_ms_p50, slo_ok_pct

    closed = pinned_run("closed")
    # a closed loop times from the send, 1 ms after the due time here
    assert admission_ttft_ms_p50.read(closed) == pytest.approx(659.0)
    # of 12: one refused, one over 2000 ms, one over 100 ms a token
    assert slo_ok_pct.read(pinned_run("open")) == pytest.approx(100.0 * 9 / 12)
    assert slo_ok_pct.read(closed) == pytest.approx(100.0 * 9 / 12)


# -- a traced run that has no trace ------------------------------------------

class StubChild:
    """``benchmark/server.py``'s side of the line protocol, as far as a
    trace goes: each ``trace_stop`` is answered with the next of ``missing``."""

    def __init__(self, missing):
        self.missing, self.calls = list(missing), []

    def call(self, cmd, timeout=300.0):
        self.calls.append((cmd, time.monotonic()))
        reply = {"reply": cmd}
        if cmd == "trace_stop":
            reply["missing"] = self.missing.pop(0)
        return reply


def traced_run(child, seconds=0.6, rehearse=False):
    ready = {"shapes": {"decode_steps": 16}, "device": {}, "numerics": {}, "setup": {}}
    args = types.SimpleNamespace(seconds=seconds, trace=1, rehearse_cpu=rehearse)
    run = bench_run.Run({"name": "a.cell"}, {}, {}, types.SimpleNamespace(LOOP="open"), ready, args)
    run.child, run.t0 = child, time.monotonic()
    asyncio.run(run._trace())
    return run


def test_a_trace_that_is_there_is_taken_once(monkeypatch):
    monkeypatch.setattr(bench_run, "TRACE_MARGIN_S", 0.05)
    child = StubChild([None])
    run = traced_run(child)
    assert [c for c, _ in child.calls] == ["trace_start", "trace_stop"]
    assert run.trace_missing is None
    run.closed = {"trace": {"busy_s": 0.1, "window_s": 0.2}}
    assert bench_run.reduced_trace(run)["busy_s"] == 0.1


def test_an_empty_trace_is_taken_once_more_inside_the_window(monkeypatch):
    monkeypatch.setattr(bench_run, "TRACE_MARGIN_S", 0.05)
    child = StubChild(["no *.xplane.pb file was written", None])
    run = traced_run(child)
    assert [c for c, _ in child.calls] == ["trace_start", "trace_stop"] * 2
    assert run.trace_missing is None
    assert child.calls[-1][1] < run.t0 + run.seconds      # inside the window


def test_two_empty_traces_end_the_run_with_the_reason_and_no_line(monkeypatch):
    monkeypatch.setattr(bench_run, "TRACE_MARGIN_S", 0.05)
    why = "no device plane has an operation on its XLA Ops line"
    child = StubChild([why, why])
    run = traced_run(child)
    assert len(child.calls) == 4 and run.trace_missing == why
    run.closed = {"trace": None}
    with pytest.raises(RuntimeError, match="XLA Ops"):
        bench_run.reduced_trace(run)
    # ``main`` turns a RuntimeError into exit 1 with the reason on standard
    # error and prints no result line
    run.closed = {}
    run.trace_missing = None
    with pytest.raises(RuntimeError, match="never taken"):
        bench_run.reduced_trace(run)


def test_no_room_for_a_second_trace_is_said():
    child = StubChild(["the file holds no device plane"])
    run = traced_run(child, seconds=0.3)                   # margin 0.5 s
    assert len(child.calls) == 2
    assert "no device plane" in run.trace_missing and "no room" in run.trace_missing


def test_a_cpu_rehearsal_needs_no_device_plane():
    child = StubChild(["the file holds no device plane"])
    run = traced_run(child, seconds=0.3, rehearse=True)
    assert len(child.calls) == 2
    run.closed = {"trace": None}
    assert bench_run.reduced_trace(run) is None


def plane(name, **lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=n, events=iter(events)) for n, events in lines.items()
    ])


@pytest.mark.parametrize("planes,want", [
    (None, "no *.xplane.pb file"),
    ([plane("/host:CPU", python=[1])], "no device plane"),
    ([plane("/device:TPU:0", **{"XLA Modules": [1], "XLA Ops": []})], "XLA Ops"),
    ([plane("/device:TPU:0", **{"XLA Ops": []}),
      plane("/device:TPU:1", **{"XLA Ops": [1]})], None),
])
def test_which_part_of_a_trace_is_missing(tmp_path, planes, want):
    if planes is not None:
        where = tmp_path / "plugins" / "profile" / "2026_01_01"
        where.mkdir(parents=True)
        (where / "host.xplane.pb").write_bytes(b"")
    got = xplane.trace_missing(
        str(tmp_path), open_profile=lambda path: types.SimpleNamespace(planes=planes)
    )
    assert (got is None) if want is None else (want in got)
