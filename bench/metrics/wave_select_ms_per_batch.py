"""Graph route: device time per batch of the waves' ``wave.select`` stage:
argmin extract from the candidate pool and the termination guard; from the
device trace, each operation given to the innermost scope of its name stack
(``spans``)."""
from spans import stage_ms_per_batch


def read(ctx):
    return stage_ms_per_batch(ctx, "wave.select")
