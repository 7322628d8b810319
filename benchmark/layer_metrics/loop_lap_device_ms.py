"""Device time of ONE lap of a decode step: the median duration of the
trace's decode program events on device 0 (``_decode_scan``:
``decode_step_ms_p50``'s events) over the tokens a row it decodes and over
``total_ut_steps``. Four of them are the step: a lap here carries its
quarter of the head, the sampler and the window's flush, because a
``jax.named_scope`` (``loop_lap``) is the HLO's ``op_name`` and in no
event's name (PERF.md section 3), so the trace's reduction cannot cut a step
at the scope. A configuration without ``total_ut_steps`` gives nothing."""

from benchmark import stats

LAYER = "device programs"
DEVICE_METRIC = True


def read(run):
    laps = run.conf.get("total_ut_steps")
    trace = run.closed.get("trace")
    if not laps or not trace:
        return None
    durations = [
        d for name, ds in trace["modules_device0_s"].items()
        if "decode" in name for d in ds
    ]
    value = stats.median(durations)
    if value is None:
        return None
    return value * 1e3 / run.shapes["decode_steps"] / int(laps)
