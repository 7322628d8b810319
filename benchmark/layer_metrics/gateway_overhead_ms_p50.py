"""The gateway's share of the time to first token, over the sessions that
FINISHED inside the window (the engine records its ``engine_ttft`` — submit
to first token inside the engine — when a session ends): the median of the
client's send-to-first-token times of the requests that ended in the window,
minus the median of the engine's own readings taken in it. Both sides then
time the same sessions from nearly the same instant; what is left is HTTP
parsing, the hand-over to the engine and the way back to the socket."""

from benchmark import samples, stats

LAYER = "gateway"
DEVICE_METRIC = True


def read(run):
    lo, hi = samples.bounds(run)
    client = stats.median([
        r.first_t - r.sent for r in run.records
        if r.first_t is not None and r.ended is not None and lo <= r.ended < hi
    ])
    engine = stats.median(run.closed["engine_ttft_s"])
    if engine is None or client is None:
        return None
    return (client - engine) * 1e3
