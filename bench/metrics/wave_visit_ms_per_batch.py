"""Graph route: device time per batch of the waves' ``wave.visit`` stage:
neighbour-row gather and the visited bitset's test and set; from the device
trace, each operation given to the innermost scope of its name stack
(``spans``)."""
from spans import stage_ms_per_batch


def read(ctx):
    return stage_ms_per_batch(ctx, "wave.visit")
