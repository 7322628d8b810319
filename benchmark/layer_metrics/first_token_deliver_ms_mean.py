"""Mean, over the window's first tokens, of the time from the ready stamp of the
request's last prompt dispatch to the host holding its token: the engine's
``engine_first_token_deliver`` summary, observed with
``engine_first_token_wait`` (the ``engine.first_token_deliver`` span of a traced
request). The three pieces sum to ``first_token_wait_ms_mean``. Sum over
count between the window's two ``/metrics`` readings."""

from benchmark import counters

LAYER = "engine host loop"
DEVICE_METRIC = True


def read(run):
    return counters.ratio(
        run, ["engine_first_token_deliver_seconds_sum"],
        "engine_first_token_deliver_seconds_count", 1e3,
    )
