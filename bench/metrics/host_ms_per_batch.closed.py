"""Engine host phase per batch (filter compile, routing, slicing, padding),
closed loop."""
from layer import host_ms_per_batch


def read(ctx):
    return host_ms_per_batch(ctx)
