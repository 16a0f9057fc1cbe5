"""Graph route: device time per batch of the traversal's ``graph.init`` stage:
upper-layer descent, pool set-up, zeroing the visited bitset; from the
device trace, each operation given to the innermost scope of its name stack
(``spans``)."""
from spans import stage_ms_per_batch


def read(ctx):
    return stage_ms_per_batch(ctx, "graph.init")
