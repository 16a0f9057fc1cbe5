"""Front end: rows per dispatch as a share of the dispatch cap, closed loop."""
from layer import batch_fill


def read(ctx):
    return batch_fill(ctx)
