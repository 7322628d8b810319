"""Share of the timed requests that met both limits of the traffic file
(``slo``: time to first token, time per output token); a failed request
misses."""

from benchmark import samples, stats

LAYER = "gateway"
DEVICE_METRIC = True


def read(run):
    slo = run.traffic.get("slo")
    timed = samples.timed_requests(run)
    if not slo or not timed:
        return None
    vocab = run.shapes["vocab_size"]
    met = 0
    for r, ttft in zip(timed, samples.ttft_s(run)):
        if not r.ok(vocab) or ttft * 1e3 > slo["ttft_ms"]:
            continue
        if len(r.tokens) > 1 and stats.time_per_output_token(
            r.arrivals[0], r.arrivals[-1], len(r.tokens)
        ) * 1e3 > slo["tpot_ms"]:
            continue
        met += 1
    return 100.0 * met / len(timed)
