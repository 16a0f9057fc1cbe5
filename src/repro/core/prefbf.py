"""Pre-filtering brute-force search (paper Sections 3.2.1 and 4.1).

On CPU the paper gathers the predicate-passing rows and scans them.  On TPU
data-dependent compaction is the enemy: the MXU prefers scanning *all* rows of
a statically-shaped block at matmul speed and masking the predicate failures
to +inf -- the arithmetic (and the results) are identical to pre-filtering,
with the filter evaluated as the compiled DNF program.  This is the fused
distance + mask + top-k scan; the Pallas kernel in kernels/filtered_topk is
the hand-tiled version of this exact loop, and ``use_pallas=True`` routes
through it.

The scan is chunked over the DB axis with a running top-k merge so the live
working set stays O(B * chunk) regardless of N (VMEM-friendly blocking; on
CPU it also bounds peak memory).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import filters as F

INF = jnp.inf
HIGHEST = jax.lax.Precision.HIGHEST


def pad_db(vectors: np.ndarray, norms: np.ndarray, ints: np.ndarray,
           floats: np.ndarray, chunk: int):
    """Pad the DB row count to a multiple of ``chunk``; padded rows get +inf
    norms so their distance is +inf and an all-False filter row."""
    n = vectors.shape[0]
    pad = (-n) % chunk
    if pad == 0:
        return vectors, norms, ints, floats
    return (
        np.concatenate([vectors, np.zeros((pad, vectors.shape[1]), vectors.dtype)]),
        np.concatenate([norms, np.full((pad,), np.inf, norms.dtype)]),
        np.concatenate([ints, np.full((pad, ints.shape[1]), -1, ints.dtype)]),
        np.concatenate([floats, np.full((pad, floats.shape[1]), np.nan, floats.dtype)]),
    )


@partial(jax.jit, static_argnames=("k", "chunk", "use_pallas"))
def prefbf_topk(vectors, norms, ints, floats, queries, programs, *,
                k: int, chunk: int = 16384, use_pallas: bool = False,
                valid=None):
    """Fused filtered brute-force top-k.

    vectors (N, d), norms (N,), ints (N, m_i), floats (N, m_f);
    queries (B, d); programs batched filter programs; ``valid`` an optional
    (B,) bool query mask (bucket padding) -- False rows return -1 / +inf.
    Returns ids (B, k) int32 (-1 for missing) and dists (B, k) (+inf missing).
    N must be a multiple of ``chunk`` (see pad_db).
    """
    if use_pallas:
        from ..kernels.filtered_topk import ops as ft_ops
        # the scan chunk becomes the kernel's n-tile; keep it VMEM-sized
        return ft_ops.filtered_topk(vectors, norms, ints, floats, queries,
                                    programs, k=k, block_n=min(chunk, 512),
                                    valid=valid)

    n, d = vectors.shape
    b = queries.shape[0]
    assert n % chunk == 0, f"N={n} not a multiple of chunk={chunk}; use pad_db"
    n_chunks = n // chunk
    qn = jnp.sum(queries * queries, axis=-1)  # (B,)

    vc = vectors.reshape(n_chunks, chunk, d)
    nc = norms.reshape(n_chunks, chunk)
    ic = ints.reshape(n_chunks, chunk, -1)
    fc = floats.reshape(n_chunks, chunk, -1)

    init = (jnp.full((b, k), INF), jnp.full((b, k), -1, jnp.int32))

    def step(carry, xs):
        # The carry holds *squared* (clamped) distances; sqrt is monotone on
        # [0, inf) so the running top-k selection is unchanged and the sqrt is
        # deferred to the final (B, k) rows after the scan.
        best_d, best_i = carry
        v, nn, ii, ff, start = xs
        # f32 contraction: the brute route is exact (on TPU the default
        # precision would round both operands to bf16)
        dot = jnp.matmul(queries, v.T, precision=HIGHEST)     # (B, chunk) MXU
        d2 = jnp.maximum(nn[None, :] + qn[:, None] - 2.0 * dot, 0.0)
        mask = F.eval_program_batched(programs, ii, ff, xp=jnp)  # (B, chunk)
        d2 = jnp.where(mask, d2, INF)
        ids = (start + jnp.arange(chunk, dtype=jnp.int32))[None, :].repeat(b, 0)
        md = jnp.concatenate([best_d, d2], axis=1)
        mi = jnp.concatenate([best_i, ids], axis=1)
        # O((k+chunk) log k) selection instead of a full argsort.  lax.top_k
        # breaks ties toward the lower index, same as the stable argsort it
        # replaces: carried entries (lower concat index) beat equal chunk
        # entries, and within a chunk the smaller db id wins.
        neg_d, order = jax.lax.top_k(-md, k)
        return (-neg_d, jnp.take_along_axis(mi, order, axis=1)), None

    starts = (jnp.arange(n_chunks, dtype=jnp.int32) * chunk)
    (best_d, best_i), _ = jax.lax.scan(step, init, (vc, nc, ic, fc, starts))
    best_d = jnp.sqrt(best_d)
    best_i = jnp.where(jnp.isfinite(best_d), best_i, -1)
    if valid is not None:
        vmask = jnp.asarray(valid, bool)[:, None]
        best_d = jnp.where(vmask, best_d, INF)
        best_i = jnp.where(vmask, best_i, -1)
    return best_i, best_d
