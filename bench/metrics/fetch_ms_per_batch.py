"""Engine per batch: the ``fetch`` span, the device-to-host copies of the
route outputs in ``PendingExecution.finish`` -- which wait out the batch's
own traversal first."""
from layer import per_batch_ms


def read(ctx):
    if ctx.hist("favor_stage_seconds", 'stage="fetch"')[1] == 0:
        return None
    return per_batch_ms(ctx, ctx.stage_s("fetch"))
