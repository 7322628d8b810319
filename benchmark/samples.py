"""The samples the metric readers share, taken from a run's request records.

Rules (ISSUE 22): an open-loop request is timed from the instant it was DUE,
late generator or not; a closed-loop request from its send. A TTFT counts
when the request was due (open) or sent (closed) inside the window; a
request that never produced a token is slower than any that did. Time per
output token is taken over requests that ended well inside the window with
at least ``MIN_TPOT_TOKENS`` tokens. Tokens per second count tokens that
ARRIVED inside the window, whoever they belong to (``out_tok_s`` tapers the
window's edges: ``stats.tapered_tokens``).
"""

from __future__ import annotations

from benchmark import stats

MIN_TPOT_TOKENS = 32


def bounds(run):
    return run.t0, run.t0 + run.seconds


def _start(run, r):
    return r.due if run.loop == "open" else r.sent


def timed_requests(run):
    lo, hi = bounds(run)
    return [
        r for r in run.records
        if r.phase == "traffic" and _start(run, r) is not None
        and lo <= _start(run, r) < hi
    ]


def ttft_s(run) -> list:
    """One reading per timed request; a miss is ``stats.MISSED``."""
    return [
        r.first_t - _start(run, r) if r.first_t is not None else stats.MISSED
        for r in timed_requests(run)
    ]


def ttft_percentile_ms(run, q: float):
    value = stats.percentile(ttft_s(run), q)
    if value is None:
        return None
    if value == stats.MISSED:
        # the percentile fell on a request with no token at all: report how
        # long the run watched it, which is longer than any real reading
        end = max(r.ended for r in run.records if r.ended is not None)
        value = end - min(_start(run, r) for r in timed_requests(run))
    return value * 1e3


def tpot_s(run) -> list:
    lo, hi = bounds(run)
    vocab = run.shapes["vocab_size"]
    return [
        stats.time_per_output_token(r.arrivals[0], r.arrivals[-1], len(r.tokens))
        for r in run.records
        if r.ok(vocab) and len(r.tokens) >= MIN_TPOT_TOKENS
        and lo <= r.arrivals[-1] < hi
    ]


def tokens_in_window(run) -> int:
    lo, hi = bounds(run)
    return sum(stats.tokens_in_window(r.arrivals, lo, hi) for r in run.records)


def lateness_s(run) -> list:
    return [r.sent - r.due for r in timed_requests(run)
            if r.due is not None and r.sent is not None]


def ticks_in_window(run) -> list:
    """Flight-recorder ticks whose (epoch) stamp lies in the window."""
    lo, hi = bounds(run)
    return [
        t for _, t in sorted(run.ticks.items())
        if lo <= t["t"] - run.epoch_offset < hi
    ]
