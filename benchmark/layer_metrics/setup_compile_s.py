"""Seconds of the set-up's program loads that the backend spent compiling
(``backend_compile_duration`` with no retrieval from the persistent cache
inside it): ``engine_program_load_compile_seconds`` as READ when the window
opens, not a delta. 0 on a warm cache; on a cold one, most of ``setup_s``."""

LAYER = "device programs"
DEVICE_METRIC = True


def read(run):
    return (run.metrics_open or {}).get("engine_program_load_compile_seconds")
