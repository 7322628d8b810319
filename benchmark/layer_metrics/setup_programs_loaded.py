"""Programs the process compiled or read from the persistent cache before the
window opened (``engine_program_loads`` as READ when the window opens, not a
delta): one a ``backend_compile_duration`` event, the event the benchmark's
own ``CompileLog`` counts as ``compile_requests``."""

LAYER = "device programs"
DEVICE_METRIC = True


def read(run):
    return (run.metrics_open or {}).get("engine_program_loads")
