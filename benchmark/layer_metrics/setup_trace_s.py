"""Seconds of the set-up's program loads that were Python tracing
(``jaxpr_trace_duration``): ``engine_program_load_trace_seconds`` as READ when
the window opens, not a delta. With ``setup_lower_s``, ``setup_compile_s`` and
``setup_cache_read_s`` it sums to ``setup_program_load_s``. The part that
fewer programs, or programs that trace less Python, would take away; no
cache touches it."""

LAYER = "device programs"
DEVICE_METRIC = True


def read(run):
    return (run.metrics_open or {}).get("engine_program_load_trace_seconds")
