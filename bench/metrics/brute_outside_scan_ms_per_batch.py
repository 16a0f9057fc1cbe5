"""Brute route: device time of ``pq_prefbf_topk`` outside its
``pq_adc_topr`` kernel, per batch -- the LUT build, the exact float32
re-rank of the best ``rerank * k`` rows and their glue (the program scopes
them ``brute.luts`` and ``brute.rerank``)."""
from layer import per_batch_ms


def read(ctx):
    total = ctx.module_s("pq_prefbf_topk")
    scan = ctx.kernel_s("pq_adc_topr")
    if total is None or scan is None:
        return None
    return per_batch_ms(ctx, total - scan)
