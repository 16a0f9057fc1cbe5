"""Device: share of the traced window with no operation on the chip,
closed loop."""
from layer import device_idle


def read(ctx):
    return device_idle(ctx)
