"""Graph route: device time of the traversal program
(``favor_graph_search``) per batch."""
from layer import per_batch_ms


def read(ctx):
    return per_batch_ms(ctx, ctx.module_s("favor_graph_search"))
