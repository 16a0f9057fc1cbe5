"""Selectivity estimate per batch: the ``estimate/wait`` span, the host's
wait for the estimate's result -- behind whatever device work was queued
before it (the other executor slot's traversal)."""
from layer import per_batch_ms


def read(ctx):
    if ctx.hist("favor_stage_seconds", 'stage="estimate/wait"')[1] == 0:
        return None
    return per_batch_ms(ctx, ctx.stage_s("estimate/wait"))
