"""Seconds from the server process's start, as the OS dates it, to the
engine's constructor: the interpreter, the imports, the backend's start and
the weights (``boot_engine_build_seconds`` as READ when the window opens).
The first of four pieces that tile the child's life up to the window
(``setup_engine_build_s``, ``setup_build_to_first_request_s``,
``setup_first_request_to_window_s``); ``setup_s`` less their sum is the
parent's time before the child exists."""

LAYER = "engine host loop"
DEVICE_METRIC = True


def read(run):
    return (run.metrics_open or {}).get("boot_engine_build_seconds")
