"""Mean, over the window's first tokens, of the device seconds of the request's
own prompt dispatches (a grouped admission counts whole for each of its
rows): the engine's
``engine_first_token_prefill_own`` summary, observed with
``engine_first_token_wait`` (the ``engine.prefill_own`` span of a traced
request). The three pieces sum to ``first_token_wait_ms_mean``. Sum over
count between the window's two ``/metrics`` readings."""

from benchmark import counters

LAYER = "engine host loop"
DEVICE_METRIC = True


def read(run):
    return counters.ratio(
        run, ["engine_first_token_prefill_own_seconds_sum"],
        "engine_first_token_prefill_own_seconds_count", 1e3,
    )
