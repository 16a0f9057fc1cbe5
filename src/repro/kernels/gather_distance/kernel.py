"""Neighbor-block distance + filter + exclusion Pallas kernel.

The graph-search expansion hot spot: given per-query neighbor-id rows
(B, M) into the DB shard, produce the adjusted distances Dis_bar (Eq. 2)
and the TD mask for each (query, neighbor) pair.

The neighbor rows are gathered by XLA in the wrapper (``ops.py``): a
one-row block per neighbor breaks the TPU's (8, 128) block tiling, so the
kernel receives each bq-query tile's neighbor block already gathered --
vectors as (bq*M, d) rows, norms and attribute planes as (bq, M).  One grid
step per query tile then does, in VMEM:

  * all-pairs dots of the tile's queries with its gathered rows on the MXU
    (bq, bq*M), masked to each query's own rows and folded back to (bq, M)
    by a 0/1 selection matmul (f32 contraction precision throughout),
  * d = sqrt(|v|^2 + |q|^2 - 2 q.v), the DNF filter program over the
    gathered attribute planes, and dbar = d + (1 - td) * D.

Padding ids (< 0) are clamped before the gather and masked to +BIG here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..filtered_topk.kernel import (BIG, HIGHEST, eval_program, fold_own_rows,
                                    selection_matrix)


def _kernel(q_ref, v_ref, sel_ref, n_ref, ai_ref, af_ref, ids_ref,
            valid_ref, imask_ref, flo_ref, fhi_ref, d_ref, od_ref, otd_ref,
            *, m: int):
    q = q_ref[...]                                          # (bq, d)
    dots = jax.lax.dot_general(q, v_ref[...], (((1,), (1,)), ((), ())),
                               precision=HIGHEST,
                               preferred_element_type=jnp.float32)  # (bq, bq*M)
    dot = fold_own_rows(dots, sel_ref[...], m)              # (bq, M)
    qn = jnp.sum(q * q, axis=1, keepdims=True)
    dist = jnp.sqrt(jnp.maximum(n_ref[...] + qn - 2.0 * dot, 0.0))

    ai, af = ai_ref[...], af_ref[...]
    td = eval_program(valid_ref[...], imask_ref[...], flo_ref[...],
                      fhi_ref[...], [ai[c] for c in range(ai.shape[0])],
                      [af[c] for c in range(af.shape[0])])
    dbar = dist + jnp.where(td, 0.0, d_ref[...])

    invalid = ids_ref[...] < 0
    od_ref[...] = jnp.where(invalid, BIG, dbar)
    otd_ref[...] = jnp.where(invalid, 0, td.astype(jnp.int32))


def gather_distance_pallas(nbr_ids, queries, rows, norms, ints, floats,
                           programs, dvec, *, block_q: int, interpret: bool):
    """nbr_ids (B, M) int32 (-1 pad); queries (B, d); the gathered neighbor
    block in kernel layout: rows (B*M, d), norms (B, M), ints (mi, B, M),
    floats (mf, B, M); programs as filtered_topk_pallas takes them; dvec
    (B, 1).  B must be a multiple of block_q (ops.py pads).
    Returns (dbar (B, M) f32 with BIG at padding, td (B, M) int32)."""
    b, m = nbr_ids.shape
    dim = queries.shape[1]
    bq = block_q
    assert b % bq == 0 and rows.shape[0] == b * m
    r = bq * m
    w = programs["valid"].shape[1]
    wi = programs["imask"].shape[1]
    wf = programs["flo"].shape[1]
    mi, mf = ints.shape[0], floats.shape[0]

    out_d, out_td = pl.pallas_call(
        functools.partial(_kernel, m=m),
        grid=(b // bq,),
        in_specs=[
            pl.BlockSpec((bq, dim), lambda i: (i, 0)),           # queries
            pl.BlockSpec((r, dim), lambda i: (i, 0)),            # rows
            pl.BlockSpec((r, m), lambda i: (0, 0)),              # selection
            pl.BlockSpec((bq, m), lambda i: (i, 0)),             # norms
            pl.BlockSpec((mi, bq, m), lambda i: (0, i, 0)),      # attrs int
            pl.BlockSpec((mf, bq, m), lambda i: (0, i, 0)),      # attrs float
            pl.BlockSpec((bq, m), lambda i: (i, 0)),             # raw ids
            pl.BlockSpec((bq, w), lambda i: (i, 0)),             # valid
            pl.BlockSpec((bq, wi), lambda i: (i, 0)),            # imask
            pl.BlockSpec((bq, wf), lambda i: (i, 0)),            # flo
            pl.BlockSpec((bq, wf), lambda i: (i, 0)),            # fhi
            pl.BlockSpec((bq, 1), lambda i: (i, 0)),             # D
        ],
        out_specs=[
            pl.BlockSpec((bq, m), lambda i: (i, 0)),
            pl.BlockSpec((bq, m), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, m), jnp.float32),
            jax.ShapeDtypeStruct((b, m), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(queries, rows, selection_matrix(r, m), norms, ints, floats, nbr_ids,
      programs["valid"], programs["imask"], programs["flo"], programs["fhi"],
      dvec)
    return out_d, out_td
