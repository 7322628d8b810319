"""Backend compile requests (JAX's own monitoring events; a persistent-cache
read is one too, and stalls its step all the same) between the window's
opening and its close. Should read 0: the warm-up reached every shape."""

LAYER = "device programs"
DEVICE_METRIC = False


def read(run):
    return len(run.closed["compiles_in_window"])
