"""Seconds of the set-up's program loads that were lowering to MLIR
(``jaxpr_to_mlir_module_duration``): ``engine_program_load_lower_seconds`` as
READ when the window opens, not a delta. Paid again by every process, warm
cache or cold: the persistent cache's key is made FROM the lowered module.
Loading serialized executables would take it away; a warmer cache does not."""

LAYER = "device programs"
DEVICE_METRIC = True


def read(run):
    return (run.metrics_open or {}).get("engine_program_load_lower_seconds")
