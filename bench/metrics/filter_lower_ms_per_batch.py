"""Engine host phase: the batch's filter compile on the host per batch, the
router's ``compile/lower`` span (every request's filter AST lowered to DNF
and written into the batch's program arrays, before their upload to the
device).  Nothing to read where the program opens no such span."""
from layer import per_batch_ms


def read(ctx):
    if ctx.hist("favor_stage_seconds", 'stage="compile/lower"')[1] == 0:
        return None
    return per_batch_ms(ctx, ctx.stage_s("compile/lower"))
