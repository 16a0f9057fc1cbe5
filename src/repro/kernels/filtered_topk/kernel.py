"""Fused filtered distance + top-k Pallas TPU kernel.

One kernel invocation scans the whole DB shard for a tile of queries:

  grid = (B/bq, N/bn); the n-axis is sequential ("arbitrary") so a running
  per-query top-k lives in VMEM scratch across n-tiles; the q-axis is
  parallel.

Per (i, j) step, entirely in VMEM:
  * load query tile (bq, d), DB tile (bn, d) + lane-dense norms (1, bn) and
    attribute rows (m_i, bn) / (m_f, bn),
  * distances via one MXU dot:  d2 = |v|^2 + |q|^2 - 2 q.v^T   (bq, bn),
    at f32 contraction precision (the brute route is exact),
  * evaluate the DNF filter program (bitmask + interval tests, branch-free,
    one (bq, bn) plane per disjunct and column),
  * PreFBF mode (exclude=False): failing rows -> +BIG (pre-filter semantics);
    exclusion mode (exclude=True): failing rows get +D (Eq. 2),
  * merge the tile into the running (bq, kp) top-k scratch by k iterations of
    masked row-min extraction (k is small: 10-100; sort-free, TPU-friendly).
    ``kp`` is k rounded up to a lane multiple, so the merge concatenates
    lane-aligned blocks and the output block is lane-dense.

TPU layout rules the block shapes follow: the last two block dims are
multiples of (8, 128) or span the whole array, so per-row arrays ride as
(1, N) lane rows, attribute columns as (m, N) rows, per-query scalars as
(B, 1) columns, and filter programs as 2-D (B, W*m) tables (``ops.py`` does
these reshapes).

VMEM working set per step: bq*d + bn*d + bq*(kp+bn) floats; defaults
(bq, bn, d) = (128, 512, <=1024) stay well under 16 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BIG = 3.0e38  # python literal: jnp scalars may not be captured by pallas kernels
INT_MAX = 2 ** 31 - 1
LANES = 128
HIGHEST = jax.lax.Precision.HIGHEST


def eval_program(valid, imask, flo, fhi, ints, floats):
    """DNF filter program of a query tile over a set of DB rows.

    valid (bq, W) f32; imask (bq, W*mi) int32 bit patterns; flo/fhi
    (bq, W*mf) f32; ``ints`` / ``floats`` are sequences of mi / mf attribute
    planes, each broadcastable against the (bq, X) output -- (1, bn) lane
    rows for a scan tile, (bq, M) for per-query gathered rows.
    Returns (bq, X) bool."""
    mi, mf = len(ints), len(floats)
    out = None
    for w in range(valid.shape[1]):
        ok = valid[:, w:w + 1] > 0                               # (bq, 1)
        for c in range(mi):
            bits = imask[:, w * mi + c:w * mi + c + 1]           # (bq, 1)
            # logical shift == the uint32 shift of filters.eval_program
            ok = ok & ((jax.lax.shift_right_logical(bits, ints[c]) & 1) == 1)
        for c in range(mf):
            col = w * mf + c
            ok = (ok & (floats[c] >= flo[:, col:col + 1])
                  & (floats[c] <= fhi[:, col:col + 1]))
        out = ok if out is None else out | ok
    return out


def selection_matrix(rows: int, m: int):
    """(rows, m) 0/1 matrix folding gathered row j into column j % m."""
    return (jnp.arange(rows)[:, None] % m
            == jnp.arange(m)[None, :]).astype(jnp.float32)


def fold_own_rows(all_pairs, sel, m: int):
    """(bq, bq*m) scores of a query tile against its whole gathered block ->
    (bq, m) scores of each query against its own m rows.  Row j of the block
    belongs to query j // m; the others are zeroed and the 0/1 selection
    matmul (``selection_matrix``, f32 precision: exact) folds the rest."""
    bq, rows = all_pairs.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (bq, rows), 1)
    lo = jax.lax.broadcasted_iota(jnp.int32, (bq, rows), 0) * m
    own = jnp.where((col >= lo) & (col < lo + m), all_pairs, 0.0)
    return jax.lax.dot_general(own, sel, (((1,), (0,)), ((), ())),
                               precision=HIGHEST,
                               preferred_element_type=jnp.float32)


def topk_merge(best_d, best_i, tile_d, tile_i, k: int):
    """Merge a (bq, bn) tile into the running (bq, kp) top-k.

    Sort-free: k rounds of row-min extraction over the lane-aligned
    concatenation, using only min reductions and selects (no argmin, no
    scatter).  Ties go to the lower column, i.e. carried entries before tile
    entries and lower DB ids first -- the order of a stable argsort.
    Columns >= k of the result hold BIG / -1."""
    d = jnp.concatenate([best_d, tile_d], axis=1)
    ids = jnp.concatenate([best_i, tile_i], axis=1)
    cols = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
    kcols = jax.lax.broadcasted_iota(jnp.int32, best_d.shape, 1)

    def body(t, carry):
        d, out_d, out_i = carry
        mn = jnp.min(d, axis=1, keepdims=True)                   # (bq, 1)
        j = jnp.min(jnp.where(d == mn, cols, INT_MAX), axis=1, keepdims=True)
        sel = cols == j
        idv = jnp.min(jnp.where(sel, ids, INT_MAX), axis=1, keepdims=True)
        out_d = jnp.where(kcols == t, mn, out_d)
        out_i = jnp.where(kcols == t, idv, out_i)
        return jnp.where(sel, BIG, d), out_d, out_i

    init = (d, jnp.full(best_d.shape, BIG, jnp.float32),
            jnp.full(best_i.shape, -1, jnp.int32))
    _, out_d, out_i = jax.lax.fori_loop(0, k, body, init)
    return out_d, out_i


def _kernel(q_ref, v_ref, n_ref, ai_ref, af_ref, valid_ref, imask_ref,
            flo_ref, fhi_ref, dvec_ref, od_ref, oi_ref, bd_ref, bi_ref,
            *, k: int, bn: int, exclude: bool):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        bd_ref[...] = jnp.full(bd_ref.shape, BIG, jnp.float32)
        bi_ref[...] = jnp.full(bi_ref.shape, -1, jnp.int32)

    q = q_ref[...]                                   # (bq, d)
    qn = jnp.sum(q * q, axis=1, keepdims=True)       # (bq, 1)
    dot = jax.lax.dot_general(q, v_ref[...], (((1,), (1,)), ((), ())),
                              precision=HIGHEST,
                              preferred_element_type=jnp.float32)  # MXU
    d2 = n_ref[...] + qn - 2.0 * dot
    dist = jnp.sqrt(jnp.maximum(d2, 0.0))            # (bq, bn)

    ai, af = ai_ref[...], af_ref[...]
    mask = eval_program(valid_ref[...], imask_ref[...], flo_ref[...],
                        fhi_ref[...],
                        [ai[c:c + 1, :] for c in range(ai.shape[0])],
                        [af[c:c + 1, :] for c in range(af.shape[0])])
    if exclude:
        dist = dist + jnp.where(mask, 0.0, dvec_ref[...])
    else:
        dist = jnp.where(mask, dist, BIG)
    # padded DB rows carry +BIG norms -> dist overflows to BIG and never wins
    dist = jnp.minimum(dist, BIG)

    ids = j * bn + jax.lax.broadcasted_iota(jnp.int32, dist.shape, 1)
    bd, bi = topk_merge(bd_ref[...], bi_ref[...], dist, ids, k)
    bd_ref[...] = bd
    bi_ref[...] = bi

    @pl.when(j == pl.num_programs(1) - 1)
    def _out():
        od_ref[...] = bd
        oi_ref[...] = bi


def filtered_topk_pallas(queries, vectors, norms, ints, floats, programs,
                         dvec, *, k: int, block_q: int, block_n: int,
                         exclude: bool, interpret: bool):
    """Launch the kernel.  Inputs arrive in kernel layout, padded to block
    multiples (ops.py does both): norms (1, N), ints (mi, N), floats
    (mf, N), programs {valid (B, W), imask (B, W*mi) int32, flo/fhi
    (B, W*mf)}, dvec (B, 1).  Returns (dists (B, kp), ids (B, kp)) with
    kp = k rounded up to a lane multiple."""
    b, dim = queries.shape
    n = vectors.shape[0]
    bq, bn = block_q, block_n
    assert b % bq == 0 and n % bn == 0
    kp = -(-k // LANES) * LANES
    w = programs["valid"].shape[1]
    wi = programs["imask"].shape[1]
    wf = programs["flo"].shape[1]
    mi, mf = ints.shape[0], floats.shape[0]
    grid = (b // bq, n // bn)

    kern = functools.partial(_kernel, k=k, bn=bn, exclude=exclude)
    out_d, out_i = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, dim), lambda i, j: (i, 0)),        # queries
            pl.BlockSpec((bn, dim), lambda i, j: (j, 0)),        # vectors
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),          # norms
            pl.BlockSpec((mi, bn), lambda i, j: (0, j)),         # attrs int
            pl.BlockSpec((mf, bn), lambda i, j: (0, j)),         # attrs float
            pl.BlockSpec((bq, w), lambda i, j: (i, 0)),          # valid
            pl.BlockSpec((bq, wi), lambda i, j: (i, 0)),         # imask
            pl.BlockSpec((bq, wf), lambda i, j: (i, 0)),         # flo
            pl.BlockSpec((bq, wf), lambda i, j: (i, 0)),         # fhi
            pl.BlockSpec((bq, 1), lambda i, j: (i, 0)),          # D per query
        ],
        out_specs=[
            pl.BlockSpec((bq, kp), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, kp), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, kp), jnp.float32),
            jax.ShapeDtypeStruct((b, kp), jnp.int32),
        ],
        scratch_shapes=[
            # running top-k state lives in VMEM across the sequential n-axis
            pltpu.VMEM((bq, kp), jnp.float32),
            pltpu.VMEM((bq, kp), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(queries, vectors, norms, ints, floats, programs["valid"],
      programs["imask"], programs["flo"], programs["fhi"], dvec)
    return out_d, out_i
