"""Selectivity estimate per batch: the ``estimate/dispatch`` span, the
host's padding and enqueue of the estimate's device program."""
from layer import per_batch_ms


def read(ctx):
    if ctx.hist("favor_stage_seconds", 'stage="estimate/dispatch"')[1] == 0:
        return None
    return per_batch_ms(ctx, ctx.stage_s("estimate/dispatch"))
