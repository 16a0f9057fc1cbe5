"""Selectivity estimate per batch: the router's ``estimate`` span, which
waits for the estimate's device program."""
from layer import per_batch_ms


def read(ctx):
    if ctx.hist("favor_stage_seconds", 'stage="estimate"')[1] == 0:
        return None
    return per_batch_ms(ctx, ctx.stage_s("estimate"))
