"""Public jit'd wrapper for the filtered_topk kernel, plus the layout helpers
the other kernel wrappers share (row padding, lane-dense attribute planes,
2-D filter-program tables)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .. import default_interpret
from .kernel import BIG, LANES, filtered_topk_pallas

SUBLANES = 8


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _pad_rows(x, n_to, fill):
    pad = n_to - x.shape[0]
    if pad == 0:
        return x
    return jnp.concatenate(
        [x, jnp.full((pad,) + x.shape[1:], fill, x.dtype)], axis=0)


def kernel_programs(programs: dict, b_pad: int) -> dict:
    """Stacked filter programs -> the kernels' 2-D tables, padded with
    always-false rows to ``b_pad``: valid (B, W) f32, imask (B, W*mi) int32
    (the uint32 bit patterns), flo/fhi (B, W*mf).  A schema without int or
    float columns gets one always-passing column, so every table is
    non-empty (see ``attr_planes``)."""
    valid = jnp.asarray(programs["valid"], jnp.float32)
    b, w = valid.shape
    imask = jnp.asarray(programs["imask"]).astype(jnp.uint32)
    flo = jnp.asarray(programs["flo"], jnp.float32)
    fhi = jnp.asarray(programs["fhi"], jnp.float32)
    if imask.shape[-1] == 0:
        imask = jnp.ones((b, w, 1), jnp.uint32)      # bit 0 of attribute 0
    if flo.shape[-1] == 0:
        flo = jnp.full((b, w, 1), -jnp.inf, jnp.float32)
        fhi = jnp.full((b, w, 1), jnp.inf, jnp.float32)
    imask = jax.lax.bitcast_convert_type(imask, jnp.int32)
    return {"valid": _pad_rows(valid, b_pad, 0),
            "imask": _pad_rows(imask.reshape(b, -1), b_pad, 0),
            "flo": _pad_rows(flo.reshape(b, -1), b_pad, jnp.inf),
            "fhi": _pad_rows(fhi.reshape(b, -1), b_pad, -jnp.inf)}


def attr_planes(ints, floats, n_pad: int):
    """(N, mi) int / (N, mf) float attribute tables -> lane-dense
    (mi, n_pad) / (mf, n_pad) planes (pad rows: int 0, float NaN, which no
    interval admits).  An empty table becomes one zero column, matching
    ``kernel_programs``' always-passing column."""
    n = ints.shape[0]
    if ints.shape[1] == 0:
        ints = jnp.zeros((n, 1), jnp.int32)
    if floats.shape[1] == 0:
        floats = jnp.zeros((n, 1), jnp.float32)
    return (_pad_rows(ints.astype(jnp.int32), n_pad, 0).T,
            _pad_rows(floats.astype(jnp.float32), n_pad, jnp.nan).T)


@partial(jax.jit, static_argnames=("k", "block_q", "block_n", "exclude",
                                   "interpret"))
def filtered_topk(vectors, norms, ints, floats, queries, programs, *,
                  k: int = 10, block_q: int = 128, block_n: int = 512,
                  dvec=None, exclude: bool = False,
                  interpret: bool | None = None, valid=None):
    """Fused filtered brute-force top-k over the DB (Pallas).

    ``valid`` is an optional (B,) bool query mask (bucket padding): False
    rows return -1 / +inf without needing a special filter program.
    Returns (ids (B, k) int32 with -1 for missing, dists (B, k) f32 with +inf
    for missing) -- same contract as core.prefbf.prefbf_topk.
    """
    if interpret is None:
        interpret = default_interpret()
    b, dim = queries.shape
    n = vectors.shape[0]
    # query tiles are whole sublane groups, DB tiles whole lane groups
    bq = _round_up(min(block_q, b), SUBLANES)
    bn = _round_up(min(block_n, n), LANES)

    # pad DB rows: BIG norms make padded rows unreachable
    n_pad = _round_up(n, bn)
    vectors = _pad_rows(vectors, n_pad, 0)
    norms = _pad_rows(norms.astype(jnp.float32), n_pad, BIG).reshape(1, n_pad)
    ints_t, floats_t = attr_planes(ints, floats, n_pad)

    # pad query rows
    b_pad = _round_up(b, bq)
    queries_p = _pad_rows(queries, b_pad, 0)
    programs_p = kernel_programs(programs, b_pad)
    if dvec is None:
        dvec = jnp.zeros((b,), jnp.float32)
    dvec_p = _pad_rows(dvec.astype(jnp.float32), b_pad, 0).reshape(b_pad, 1)

    # HLO-metadata profiling scope, the innermost around the pallas_call:
    # the device trace names the kernel by it (trace-time only)
    with jax.named_scope("favor.filtered_topk"):
        out_d, out_i = filtered_topk_pallas(
            queries_p, vectors, norms, ints_t, floats_t, programs_p, dvec_p,
            k=k, block_q=bq, block_n=bn, exclude=exclude, interpret=interpret)
    out_d, out_i = out_d[:b, :k], out_i[:b, :k]
    missing = out_d >= BIG
    if valid is not None:
        missing = missing | ~jnp.asarray(valid, bool)[:, None]
    return (jnp.where(missing, -1, out_i),
            jnp.where(missing, jnp.inf, out_d))
