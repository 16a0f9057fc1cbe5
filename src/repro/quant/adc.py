"""Asymmetric distance computation: compressed filtered scans + exact re-rank.

Online, each query builds one lookup table of squared sub-distances to every
centroid (``build_luts``: (B, M, K)); scanning the DB then reads only the
uint8 codes -- ADC distance is M table lookups + adds per vector instead of a
d-dim dot product.  The scan is chunked with a running top-R merge exactly
like core.prefbf (same DNF filter-program masking, same +inf conventions for
failing and padded rows), but it keeps R = rerank * k candidates instead of
k: ADC distances are approximations, so the final answer is an exact float32
re-rank of those R rows (the only full-precision reads on the whole path).

``use_pallas=True`` routes the scan through kernels/pq_adc, which fuses the
LUT gather-accumulate (as K-wide one-hot matmuls feeding the MXU), the
filter mask and the running top-R entirely in VMEM.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..core import filters as F

INF = jnp.inf
HIGHEST = jax.lax.Precision.HIGHEST


def build_luts(centroids, queries):
    """Per-query squared-distance tables.

    centroids (M, K, dsub); queries (B, d) with d <= M * dsub -- the query is
    zero-padded on the feature tail exactly like the encoded vectors, so the
    padded dims contribute |c_pad|^2 identically to every row and preserve
    the ADC ranking.  Returns (B, M, K) float32.
    """
    m, k, dsub = centroids.shape
    b, d = queries.shape
    pad = m * dsub - d
    if pad:
        queries = jnp.concatenate(
            [queries, jnp.zeros((b, pad), jnp.float32)], axis=1)
    qs = queries.reshape(b, m, dsub)
    qn = jnp.sum(qs * qs, axis=-1)            # (B, M)
    cn = jnp.sum(centroids * centroids, -1)   # (M, K)
    dot = jnp.einsum("bmd,mkd->bmk", qs, centroids, precision=HIGHEST)
    return jnp.maximum(qn[:, :, None] + cn[None, :, :] - 2.0 * dot, 0.0)


def _merge_topr(best_d, best_i, tile_d, tile_i, r: int):
    d = jnp.concatenate([best_d, tile_d], axis=1)
    i = jnp.concatenate([best_i, tile_i], axis=1)
    order = jnp.argsort(d, axis=1)[:, :r]
    return (jnp.take_along_axis(d, order, axis=1),
            jnp.take_along_axis(i, order, axis=1))


def _adc_scan(codes, norms, ints, floats, luts, programs, *, r: int,
              chunk: int):
    """Chunked compressed scan -> top-R (adc_d2 (B,R), ids (B,R))."""
    n, m = codes.shape
    b, _, ksub = luts.shape
    assert n % chunk == 0, f"N={n} not a multiple of chunk={chunk}"
    n_chunks = n // chunk
    luts_flat = luts.reshape(b, m * ksub)

    cc = codes.reshape(n_chunks, chunk, m)
    nc = norms.reshape(n_chunks, chunk)
    ic = ints.reshape(n_chunks, chunk, -1)
    fc = floats.reshape(n_chunks, chunk, -1)
    init = (jnp.full((b, r), INF), jnp.full((b, r), -1, jnp.int32))

    def step(carry, xs):
        best_d, best_i = carry
        c, nn, ii, ff, start = xs
        # one flat gather on the (B, M*K) table -- subspace mm's code
        # addresses entry mm*K + code (see PqAdcScorer.score_block)
        flat = (c.astype(jnp.int32)
                + (jnp.arange(m, dtype=jnp.int32) * ksub)[None, :])
        g = jnp.take_along_axis(luts_flat[:, None, :], flat[None], axis=2)
        adc = jnp.sum(g.astype(jnp.float32), axis=-1)        # (B, chunk)
        mask = F.eval_program_batched(programs, ii, ff, xp=jnp)
        ok = mask & jnp.isfinite(nn)[None, :]                # padded rows out
        adc = jnp.where(ok, adc, INF)
        ids = (start + jnp.arange(chunk, dtype=jnp.int32))[None, :].repeat(b, 0)
        return _merge_topr(best_d, best_i, adc, ids, r), None

    starts = jnp.arange(n_chunks, dtype=jnp.int32) * chunk
    (best_d, best_i), _ = jax.lax.scan(step, init, (cc, nc, ic, fc, starts))
    return best_d, jnp.where(jnp.isfinite(best_d), best_i, -1)


def _exact_rerank(vectors, norms, queries, cand_i, *, k: int, valid=None):
    """Exact float32 top-k over the (B, R) ADC candidate lists.  ``valid``
    is the optional (B,) bool query mask: False rows return -1 / +inf."""
    safe = jnp.maximum(cand_i, 0)
    v = vectors[safe]                                        # (B, R, d)
    vn = norms[safe]
    qn = jnp.sum(queries * queries, axis=-1)
    # batched mat-vec as multiply + reduce: bit-identical across batch
    # sizes (bucket padding), unlike a dot_general (see search._pairwise_dist)
    dot = jnp.sum(queries[:, None, :] * v, axis=-1)
    dist = jnp.sqrt(jnp.maximum(vn + qn[:, None] - 2.0 * dot, 0.0))
    dist = jnp.where(cand_i >= 0, dist, INF)
    order = jnp.argsort(dist, axis=1)[:, :k]
    out_d = jnp.take_along_axis(dist, order, axis=1)
    out_i = jnp.take_along_axis(cand_i, order, axis=1)
    if valid is not None:
        vmask = jnp.asarray(valid, bool)[:, None]
        out_d = jnp.where(vmask, out_d, INF)
    return jnp.where(jnp.isfinite(out_d), out_i, -1), out_d


@partial(jax.jit, static_argnames=("k", "rerank", "chunk", "use_pallas"))
def pq_prefbf_topk(codes, norms, ints, floats, queries, programs, centroids,
                   vectors, *, k: int, rerank: int = 4, chunk: int = 8192,
                   use_pallas: bool = False, valid=None):
    """Compressed filtered brute-force top-k with exact re-rank.

    codes (N, M) uint8; norms/ints/floats/vectors: the padded DB arrays from
    prefbf.pad_db (norms also gate out padded rows here, since a padded code
    row is a legal code word); queries (B, d); programs batched filter
    programs; centroids (M, K, dsub); ``valid`` an optional (B,) bool query
    mask (bucket padding) -- False rows return -1 / +inf.

    Same contract as prefbf_topk: ids (B, k) int32 (-1 missing) and exact
    float32 dists (B, k) (+inf missing).
    """
    r = max(k, rerank * k)
    # brute.luts / brute.rerank: profiler scopes around the work outside
    # the scan, read from an operation's name stack (favor.pq_adc_topr
    # names the kernel itself)
    with jax.named_scope("brute.luts"):
        luts = build_luts(centroids, queries)
    if use_pallas:
        from ..kernels.pq_adc import ops as pq_ops
        # the kernel's VMEM budget is sized for bn<=512 tiles (it builds a
        # (bn, K) one-hot per subspace); don't forward the scan chunk as-is
        cand_i, _ = pq_ops.pq_adc_topr(codes, norms, ints, floats, luts,
                                       programs, r=r,
                                       block_n=min(chunk, 512), valid=valid)
    else:
        _, cand_i = _adc_scan(codes, norms, ints, floats, luts, programs,
                              r=r, chunk=chunk)
    with jax.named_scope("brute.rerank"):
        return _exact_rerank(vectors, norms, queries, cand_i, k=k,
                             valid=valid)


@partial(jax.jit, static_argnames=("k", "rerank", "chunk"))
def sq_prefbf_topk(codes, lo, scale, norms, ints, floats, queries, programs,
                   vectors, *, k: int, rerank: int = 4, chunk: int = 8192,
                   valid=None):
    """Scalar-quantization fallback scan: per-chunk dequantize + matmul.

    codes (N, d) uint8.  The approximate distance is computed against the
    int8-dequantized vectors (still 4x fewer bytes streamed than float32);
    candidates then get the same exact float32 re-rank as the PQ path.
    ``valid`` is the optional (B,) bool query mask (bucket padding).
    """
    r = max(k, rerank * k)
    n, d = codes.shape
    b = queries.shape[0]
    assert n % chunk == 0, f"N={n} not a multiple of chunk={chunk}"
    n_chunks = n // chunk
    qn = jnp.sum(queries * queries, axis=-1)

    cc = codes.reshape(n_chunks, chunk, d)
    nc = norms.reshape(n_chunks, chunk)
    ic = ints.reshape(n_chunks, chunk, -1)
    fc = floats.reshape(n_chunks, chunk, -1)
    init = (jnp.full((b, r), INF), jnp.full((b, r), -1, jnp.int32))

    def step(carry, xs):
        best_d, best_i = carry
        c, nn, ii, ff, start = xs
        deq = c.astype(jnp.float32) * scale[None, :] + lo[None, :]
        dn = jnp.sum(deq * deq, axis=-1)                     # (chunk,)
        d2 = (dn[None, :] + qn[:, None]
              - 2.0 * jnp.matmul(queries, deq.T, precision=HIGHEST))
        d2 = jnp.maximum(d2, 0.0)
        mask = F.eval_program_batched(programs, ii, ff, xp=jnp)
        ok = mask & jnp.isfinite(nn)[None, :]
        d2 = jnp.where(ok, d2, INF)
        ids = (start + jnp.arange(chunk, dtype=jnp.int32))[None, :].repeat(b, 0)
        return _merge_topr(best_d, best_i, d2, ids, r), None

    starts = jnp.arange(n_chunks, dtype=jnp.int32) * chunk
    (best_d, cand_i), _ = jax.lax.scan(step, init, (cc, nc, ic, fc, starts))
    cand_i = jnp.where(jnp.isfinite(best_d), cand_i, -1)
    return _exact_rerank(vectors, norms, queries, cand_i, k=k, valid=valid)
