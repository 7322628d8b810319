"""Seconds the process spent loading programs before the window opened:
tracing, lowering and compiling or reading from the persistent cache, as
JAX's monitoring reports them to the program's own listener
(``engine_program_load_seconds`` as READ when the window opens, not a delta:
everything before it is set-up). The part of ``setup_s`` that a warmer cache
or fewer executables would take away."""

LAYER = "device programs"
DEVICE_METRIC = True


def read(run):
    return (run.metrics_open or {}).get("engine_program_load_seconds")
