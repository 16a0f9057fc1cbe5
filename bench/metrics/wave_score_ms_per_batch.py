"""Graph route: device time per batch of the waves' ``wave.score`` stage:
scoring the neighbour block (the ``gather_distance`` kernel and its
gathers); from the device trace, each operation given to the innermost scope
of its name stack (``spans``)."""
from spans import stage_ms_per_batch


def read(ctx):
    return stage_ms_per_batch(ctx, "wave.score")
