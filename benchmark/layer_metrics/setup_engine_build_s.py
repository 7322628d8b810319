"""Seconds inside the engine's constructor: the cache's pool, the plan, the
step programs' wrappers (``boot_engine_built_seconds`` less
``boot_engine_build_seconds``, as READ when the window opens)."""

LAYER = "engine host loop"
DEVICE_METRIC = True


def read(run):
    got = run.metrics_open or {}
    build = got.get("boot_engine_build_seconds")
    built = got.get("boot_engine_built_seconds")
    if build is None or built is None:
        return None
    return built - build
