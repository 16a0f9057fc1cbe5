"""Public jit'd wrapper for the pq_adc kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .. import default_interpret
from ..filtered_topk.ops import (LANES, SUBLANES, _pad_rows, _round_up,
                                 attr_planes, kernel_programs)
from .kernel import BIG, pq_adc_gather_pallas, pq_adc_pallas

# one LUT tile's VMEM budget (double-buffered by the pipeline): wide tables
# (M*K entries per query) get fewer queries per tile
LUT_TILE_BYTES = 2 * 1024 * 1024


@partial(jax.jit, static_argnames=("block_q", "interpret"))
def pq_adc_gather(codes, luts, nbr_ids, *, block_q: int = 8,
                  interpret: bool | None = None):
    """Graph-expansion ADC scoring (block-gather Pallas).

    codes (N, M) uint8/int32; luts (B, M, K) from quant.adc.build_luts (f32
    or bf16 -- accumulation is f32 either way); nbr_ids (B, M0) int32
    per-query neighbor ids (-1 pad -> +inf).  Returns adc_d2 (B, M0) float32
    -- squared approximate distances; the traversal masks pad/visited
    entries and re-ranks its final candidates exactly.

    The neighbor code rows are gathered here (an XLA gather of M bytes per
    row, from the stored uint8 layout) and widened only after the gather.
    B is padded up to a block_q multiple with -1 ids (scored then sliced
    off); block_q is also the kernel's redundant-scoring factor, so keep it
    at one sublane group.
    """
    b, m, ksub = luts.shape
    m0 = nbr_ids.shape[1]
    if interpret is None:
        interpret = default_interpret()
    # named_scope stamps the kernel into HLO op metadata at trace time, so
    # a jax.profiler capture attributes its device time by name -- compiled
    # executables carry it for free
    with jax.named_scope("favor.pq_adc_gather"):
        bq = _round_up(min(block_q, b), SUBLANES)
        b_pad = _round_up(b, bq)
        ids = _pad_rows(nbr_ids.astype(jnp.int32), b_pad, -1)
        luts_p = _pad_rows(luts.reshape(b, m * ksub), b_pad, 0)
        rows = codes[jnp.maximum(ids, 0).reshape(-1)].astype(jnp.int32)
        out = pq_adc_gather_pallas(ids, luts_p, rows, block_q=bq,
                                   interpret=interpret)[:b]
        return jnp.where(out >= BIG, jnp.inf, out)


@partial(jax.jit, static_argnames=("r", "block_q", "block_n", "interpret"))
def pq_adc_topr(codes, norms, ints, floats, luts, programs, *,
                r: int = 40, block_q: int = 128, block_n: int = 512,
                interpret: bool | None = None, valid=None):
    """Fused compressed filtered top-R candidate scan (Pallas).

    codes (N, M) uint8/int32; norms (N,) float32 (+inf/BIG rows are treated
    as padding); luts (B, M, K) from quant.adc.build_luts; programs batched
    filter programs; ``valid`` an optional (B,) bool query mask (bucket
    padding): False rows return -1 / +inf.  Returns (ids (B, R) int32 with
    -1 for missing, adc_d2 (B, R) f32 with +inf for missing) -- ADC
    distances are squared and approximate; callers re-rank exactly
    (quant/adc.py).
    """
    b, m, ksub = luts.shape
    n = codes.shape[0]
    lut_row = m * ksub * luts.dtype.itemsize
    bq = _round_up(min(block_q, b, max(SUBLANES, LUT_TILE_BYTES // lut_row)),
                   SUBLANES)
    bn = _round_up(min(block_n, n), LANES)

    # pad DB rows: BIG norms mark padded rows, any code word is fine.
    # codes keep their stored (uint8) dtype -- the kernel widens in-register,
    # so every code tile DMA moves 1 byte per entry instead of 4
    n_pad = _round_up(n, bn)
    codes = _pad_rows(codes, n_pad, 0)
    norms = _pad_rows(jnp.minimum(norms.astype(jnp.float32), BIG), n_pad,
                      BIG).reshape(1, n_pad)
    ints_t, floats_t = attr_planes(ints, floats, n_pad)

    # pad query rows
    b_pad = _round_up(b, bq)
    luts_p = _pad_rows(luts.reshape(b, m * ksub), b_pad, 0)
    programs_p = kernel_programs(programs, b_pad)

    if interpret is None:
        interpret = default_interpret()
    with jax.named_scope("favor.pq_adc_topr"):
        out_d, out_i = pq_adc_pallas(
            luts_p, codes, norms, ints_t, floats_t, programs_p,
            r=r, block_q=bq, block_n=bn, interpret=interpret)
    out_d, out_i = out_d[:b, :r], out_i[:b, :r]
    missing = out_d >= BIG
    if valid is not None:
        missing = missing | ~jnp.asarray(valid, bool)[:, None]
    return (jnp.where(missing, -1, out_i),
            jnp.where(missing, jnp.inf, out_d))
