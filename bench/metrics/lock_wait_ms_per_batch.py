"""Engine per batch: time spent waiting for the engine lock, over its three
sites (``favor_engine_lock_wait_seconds{site=serve|finish|hook}``)."""
from layer import per_batch_ms

SITES = ("serve", "finish", "hook")


def read(ctx):
    name = "favor_engine_lock_wait_seconds"
    if name not in ctx.registry["histograms"]:
        return None
    return per_batch_ms(ctx, sum(ctx.hist(name, f'site="{s}"')[0]
                                 for s in SITES))
