"""Fused PQ asymmetric-distance + filter mask + running top-R Pallas kernel.

Compressed-domain sibling of kernels/filtered_topk: one invocation scans the
whole code table for a tile of queries,

  grid = (B/bq, N/bn); the n-axis is sequential so the running per-query
  top-R candidate list lives in VMEM scratch across n-tiles.

Per (i, j) step, entirely in VMEM:
  * load the query LUT tile (bq, M*K) and the code tile (bn, M) **uint8**
    (codes stream from HBM in their stored byte layout -- widening to int32
    happens in-register, never in memory traffic),
  * ADC accumulation as M one-hot matmuls: for each subspace the code column
    becomes a (bn, K) one-hot and contracts with the (bq, K) LUT slice on the
    MXU -- a gather expressed as arithmetic, since TPU Pallas has no
    in-kernel vector gather,
  * evaluate the DNF filter program on the lane-dense attribute planes
    (shared helper from filtered_topk) and mask failing + padded rows
    (norm >= BIG) to BIG,
  * merge into the running (bq, Rp) top-R scratch (R = rerank * k, Rp its
    lane-aligned width; the exact float32 re-rank happens outside, in
    quant/adc.py).

VMEM working set per step: bq*M*K + bn*M + bn*K + bq*(Rp+bn) floats;
defaults (bq, bn, M, K) = (128, 512, 8, 256) stay well under 16 MB (the
wrapper shrinks bq for wide LUTs).

The graph-route sibling ``pq_adc_gather_pallas`` scores per-query neighbor
blocks.  The (B, M0) neighbor code rows are gathered by XLA in the wrapper
(M bytes per row; a one-row block would break the TPU's (8, 128) block
tiling), so each grid step holds a bq-query tile's whole (bq*M0, M) code
block.  It is scored against the LUT tile with the same M one-hot MXU
matmuls the full-scan kernel uses -- every query scores every staged row --
and a selection matmul keeps each query's own M0 columns.  The MXU form
does bq x redundant math but turns the LUT lookups into M dense
(bq, K) x (K, bq*M0) contractions; keep bq at one sublane group (8).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..filtered_topk.kernel import (BIG, HIGHEST, LANES, eval_program,
                                    fold_own_rows, selection_matrix,
                                    topk_merge)


def _adc_block(lut, codes, m: int, ksub: int):
    """(bq, M*K) LUT tile x (R, M) int32 code rows -> (bq, R) ADC sums.

    A bf16 LUT multiplies a bf16 one-hot (exact products, f32 accumulate);
    an f32 LUT contracts at f32 precision, so both equal the table sums."""
    kcols = jax.lax.broadcasted_iota(jnp.int32, (1, ksub), 1)
    exact = HIGHEST if lut.dtype == jnp.float32 else None
    acc = jnp.zeros((lut.shape[0], codes.shape[0]), jnp.float32)
    for mm in range(m):                 # static unroll: M is small (<= 64)
        oh = (codes[:, mm:mm + 1] == kcols).astype(lut.dtype)    # (R, K)
        acc = acc + jax.lax.dot_general(
            lut[:, mm * ksub:(mm + 1) * ksub], oh,
            (((1,), (1,)), ((), ())), precision=exact,
            preferred_element_type=jnp.float32)                   # MXU
    return acc


def _kernel(lut_ref, c_ref, n_ref, ai_ref, af_ref, valid_ref, imask_ref,
            flo_ref, fhi_ref, od_ref, oi_ref, bd_ref, bi_ref,
            *, r: int, bn: int, m: int, ksub: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        bd_ref[...] = jnp.full(bd_ref.shape, BIG, jnp.float32)
        bi_ref[...] = jnp.full(bi_ref.shape, -1, jnp.int32)

    # (bn, M) uint8 -> int32 in-register
    acc = _adc_block(lut_ref[...], c_ref[...].astype(jnp.int32), m, ksub)

    ai, af = ai_ref[...], af_ref[...]
    mask = eval_program(valid_ref[...], imask_ref[...], flo_ref[...],
                        fhi_ref[...],
                        [ai[c:c + 1, :] for c in range(ai.shape[0])],
                        [af[c:c + 1, :] for c in range(af.shape[0])])
    ok = mask & (n_ref[...] < BIG)            # padded rows carry BIG norms
    dist = jnp.minimum(jnp.where(ok, acc, BIG), BIG)

    ids = j * bn + jax.lax.broadcasted_iota(jnp.int32, dist.shape, 1)
    bd, bi = topk_merge(bd_ref[...], bi_ref[...], dist, ids, r)
    bd_ref[...] = bd
    bi_ref[...] = bi

    @pl.when(j == pl.num_programs(1) - 1)
    def _out():
        od_ref[...] = bd
        oi_ref[...] = bi


def _gather_kernel(lut_ref, c_ref, ids_ref, sel_ref, o_ref,
                   *, m0: int, m: int, ksub: int):
    """Score one bq-query tile's gathered (bq*M0, M) code block.

    The (bq, bq*M0) all-pairs ADC matrix is folded to each query's own M0
    rows (``fold_own_rows``)."""
    acc = _adc_block(lut_ref[...], c_ref[...], m, ksub)      # (bq, bq*M0)
    out = fold_own_rows(acc, sel_ref[...], m0)
    o_ref[...] = jnp.where(ids_ref[...] < 0, BIG, out)


def pq_adc_gather_pallas(nbr_ids, luts, codes, *, block_q: int,
                         interpret: bool):
    """Block-gather ADC scoring (graph-route sibling of pq_adc_pallas).

    nbr_ids (B, M0) int32 (-1 pad); luts (B, M*K) flattened (f32 or bf16);
    codes (B*M0, M) int32 -- the neighbor code rows, already gathered (row
    b*M0 + j holds neighbor j of query b).  B must be a multiple of
    block_q (ops.py pads).  Returns adc_d2 (B, M0) float32 with BIG at
    padding.
    """
    b, m0 = nbr_ids.shape
    m = codes.shape[1]
    mk = luts.shape[1]
    ksub = mk // m
    bq = block_q
    assert b % bq == 0 and codes.shape[0] == b * m0
    rows = bq * m0

    return pl.pallas_call(
        functools.partial(_gather_kernel, m0=m0, m=m, ksub=ksub),
        grid=(b // bq,),
        in_specs=[
            pl.BlockSpec((bq, mk), lambda i: (i, 0)),            # LUT tile
            pl.BlockSpec((rows, m), lambda i: (i, 0)),           # code rows
            pl.BlockSpec((bq, m0), lambda i: (i, 0)),            # raw ids
            pl.BlockSpec((rows, m0), lambda i: (0, 0)),          # selection
        ],
        out_specs=pl.BlockSpec((bq, m0), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, m0), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(luts, codes, nbr_ids, selection_matrix(rows, m0))


def pq_adc_pallas(luts, codes, norms, ints, floats, programs, *, r: int,
                  block_q: int, block_n: int, interpret: bool):
    """Launch the kernel.  Inputs arrive in kernel layout, padded to block
    multiples (ops.py does both): luts (B, M*K) flattened, codes (N, M),
    norms (1, N), ints (mi, N), floats (mf, N), programs as
    filtered_topk_pallas takes them.  Returns (adc_d2 (B, Rp), ids (B, Rp)),
    Rp = r rounded up to a lane multiple."""
    b, mk = luts.shape
    n, m = codes.shape
    ksub = mk // m
    bq, bn = block_q, block_n
    assert b % bq == 0 and n % bn == 0
    rp = -(-r // LANES) * LANES
    w = programs["valid"].shape[1]
    wi = programs["imask"].shape[1]
    wf = programs["flo"].shape[1]
    mi, mf = ints.shape[0], floats.shape[0]
    grid = (b // bq, n // bn)

    kern = functools.partial(_kernel, r=r, bn=bn, m=m, ksub=ksub)
    out_d, out_i = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, mk), lambda i, j: (i, 0)),         # LUTs
            pl.BlockSpec((bn, m), lambda i, j: (j, 0)),          # codes
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),          # norms
            pl.BlockSpec((mi, bn), lambda i, j: (0, j)),         # attrs int
            pl.BlockSpec((mf, bn), lambda i, j: (0, j)),         # attrs float
            pl.BlockSpec((bq, w), lambda i, j: (i, 0)),          # valid
            pl.BlockSpec((bq, wi), lambda i, j: (i, 0)),         # imask
            pl.BlockSpec((bq, wf), lambda i, j: (i, 0)),         # flo
            pl.BlockSpec((bq, wf), lambda i, j: (i, 0)),         # fhi
        ],
        out_specs=[
            pl.BlockSpec((bq, rp), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, rp), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, rp), jnp.float32),
            jax.ShapeDtypeStruct((b, rp), jnp.int32),
        ],
        scratch_shapes=[
            # running top-R state lives in VMEM across the sequential n-axis
            pltpu.VMEM((bq, rp), jnp.float32),
            pltpu.VMEM((bq, rp), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(luts, codes, norms, ints, floats, programs["valid"],
      programs["imask"], programs["flo"], programs["fhi"])
    return out_d, out_i
