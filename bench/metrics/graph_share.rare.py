"""Selector: share (%) of the window's requests served by the graph route
(``favor_requests_total`` by route).  Under rare filters, all below the
selector's threshold, it should read 0."""


def read(ctx):
    graph = ctx.counter("favor_requests_total", 'route="graph"')
    brute = ctx.counter("favor_requests_total", 'route="brute"')
    if graph + brute == 0:
        return None
    return 100.0 * graph / (graph + brute)
