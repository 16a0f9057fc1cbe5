"""Graph route: device time per batch of the waves' ``wave.filter`` stage:
attribute gathers, filter evaluation and the exclusion-distance compose;
from the device trace, each operation given to the innermost scope of its
name stack (``spans``)."""
from spans import stage_ms_per_batch


def read(ctx):
    return stage_ms_per_batch(ctx, "wave.filter")
