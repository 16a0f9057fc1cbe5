"""Engine per batch: the ``slow_log`` span, the slow-query log's filter
signatures and ring writes, under the engine lock."""
from layer import per_batch_ms


def read(ctx):
    # every traced batch of a program that has the span records ``fetch``;
    # a batch under the slow-query threshold records no ``slow_log``
    if ctx.hist("favor_stage_seconds", 'stage="fetch"')[1] == 0:
        return None
    return per_batch_ms(ctx, ctx.stage_s("slow_log"))
