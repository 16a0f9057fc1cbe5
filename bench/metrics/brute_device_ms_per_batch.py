"""Brute route: device time of the compressed scan's program
(``pq_prefbf_topk``: LUT build, ``pq_adc_topr`` scan, exact re-rank) per
batch."""
from layer import per_batch_ms


def read(ctx):
    return per_batch_ms(ctx, ctx.module_s("pq_prefbf_topk"))
