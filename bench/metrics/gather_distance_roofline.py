"""Kernel: ``gather_distance`` (the graph route's neighbour-block scoring)
against its roofline.

Work the algorithm needs, from the hop counter: every hop expands one node
and scores its ``M0 = 2 M`` base-layer neighbours, reading each one's
float32 vector, squared norm, attribute columns and id once, and computing
one d-long dot product (2 d operations).  Time: device seconds of the
Pallas kernel ``favor.gather_distance``."""
from layer import attr_bytes, roofline


def read(ctx):
    hops = ctx.counter("favor_graph_hops_total")
    d = ctx.cfg["corpus"]["dim"]
    m0 = 2 * ctx.cfg["hnsw"]["M"]
    rows = hops * m0
    return roofline(ctx, "gather_distance_roofline",
                    ctx.kernel_s("gather_distance"),
                    ops=rows * 2 * d,
                    nbytes=rows * (4 * d + 4 + attr_bytes(ctx.cfg) + 4))
