"""Graph route: traversal waves (while-loop iterations over the lane
ladder) per batch, from ``favor_graph_waves``; every request of a batch
reports its batch's count."""


def read(ctx):
    total, n = ctx.hist("favor_graph_waves", 'route="graph"')
    return total / n if n else None
