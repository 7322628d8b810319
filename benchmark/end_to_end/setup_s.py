"""Start of the process to the start of the window: imports, weights, engine,
the logits check, warm-up of the cell's shapes and the traffic's lead-in;
in a run that compiles, the compilation."""

DEVICE_METRIC = True


def read(run):
    return run.setup_s
