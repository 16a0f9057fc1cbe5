"""Selectivity estimate: the selector's exact check per batch, the
router's ``estimate/check`` span (pad, dispatch and sync of the exact match
count of the requests whose estimate fell in the check band).  Nothing to
read where no batch of the window had such a request."""
from layer import per_batch_ms


def read(ctx):
    if ctx.hist("favor_stage_seconds", 'stage="estimate/check"')[1] == 0:
        return None
    return per_batch_ms(ctx, ctx.stage_s("estimate/check"))
