"""Mean, over the window's first tokens, of the part of a first token's wait in
which the device ran other rows' work, or nothing: the wait less the two
pieces beside it: the engine's
``engine_first_token_prefill_wait`` summary, observed with
``engine_first_token_wait`` (the ``engine.prefill_wait`` span of a traced
request). The three pieces sum to ``first_token_wait_ms_mean``. Sum over
count between the window's two ``/metrics`` readings."""

from benchmark import counters

LAYER = "engine host loop"
DEVICE_METRIC = True


def read(run):
    return counters.ratio(
        run, ["engine_first_token_prefill_wait_seconds_sum"],
        "engine_first_token_prefill_wait_seconds_count", 1e3,
    )
