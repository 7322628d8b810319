"""Seconds of the set-up's program loads that were reads of the persistent
compile cache (``backend_compile_duration`` events inside which the same
thread reported ``cache_retrieval_time_sec``):
``engine_program_load_cache_read_seconds`` as READ when the window opens, not
a delta. The part of a warm ``setup_s`` that depends on what the machine's
disk and page cache did before the run, not on the program."""

LAYER = "device programs"
DEVICE_METRIC = True


def read(run):
    return (run.metrics_open or {}).get("engine_program_load_cache_read_seconds")
