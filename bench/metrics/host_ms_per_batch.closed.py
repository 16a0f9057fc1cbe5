"""Engine host phase per batch (filter compile, cache lookup, routing,
slicing, padding), closed loop: the ``graph`` and ``brute`` spans less the
``graph/search`` and ``brute/search`` children that enqueue the device
work."""
from layer import host_ms_per_batch


def read(ctx):
    return host_ms_per_batch(ctx)
