"""Selectivity-driven search selector (paper Section 4).

Routes each query by its sampled selectivity estimate: ``p_hat < lambda``
(= 1%, paper section 4.1) goes to the pre-filtering brute-force scan, the rest
to the exclusion-distance graph search.  The graph route has no recall to
offer below lambda, so a filter whose true selectivity is under lambda must
never reach it; the sample alone cannot promise that near lambda (Eq. 1 is
tens of percent there, selectivity.py).  So an estimate at or above lambda
that Eq. 1's bound, at ``CHECK_Z`` standard deviations, cannot hold above
lambda is *checked*: the filter's exact match count over the full attribute
columns (``exact_batched``, one device program) decides its route instead.
Estimates below lambda, and those the bound clears, route as the paper's
selector does; ``p_hat`` stays the input to the exclusion distance either
way.  A request whose true selectivity is at least lambda therefore takes
the same route as under the estimate-only rule.

The estimate itself is one vectorized filter-program evaluation over the
fixed sample block (selectivity.py); under the sharded serve path each shard
holds a slice of the sample and the counts are psum-combined so every shard
takes the same routing decision deterministically.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import filters as F
from .selectivity import relative_error

# Confidence of the estimate-only graph decision, in standard deviations of
# Eq. 1 (sigma = p_hat * relative_error).  The sample alone sends a request
# to the graph once p_hat - CHECK_Z * sigma >= lambda; every estimate in
# [lambda, that bound) is checked exactly.  At the 328-row sample of 2^15
# rows the bound is 15 sample hits, so the band is 4 to 14 hits.  The hit
# count is hypergeometric: a filter with true p just under lambda reaches 15
# hits with probability 1.3e-6 (true p = 0.8%: 7.6e-8), against 42% for the
# 4 hits of the estimate-only rule.  A 5% filter lands in the band about one
# time in three, and pays one exact count when it does.
CHECK_Z = 3.0


@dataclass(frozen=True)
class SelectorConfig:
    lam: float = 0.01          # lambda threshold (section 4.1)
    sample_rate: float = 0.01  # section 4.2: 1% sampling
    min_sample: int = 256
    max_sample: int = 65536
    p_min: float = 1e-4        # clamp for D computation off-route


@jax.jit
def estimate_batched(programs, sample_ints, sample_floats):
    """(B,) p_hat over the pre-drawn sample rows (jit; runs every batch)."""
    mask = F.eval_program_batched(programs, sample_ints, sample_floats, xp=jnp)
    return jnp.mean(mask.astype(jnp.float32), axis=1)


@partial(jax.jit, static_argnames=("chunk",))
def exact_batched(programs, ints, floats, norms, *, chunk: int):
    """(B,) exact selectivity over every row: rows that pass each program,
    over rows with a finite norm (padding and tombstones carry +inf).  The
    rows are scanned ``chunk`` at a time, so the (B, W, chunk) program
    evaluation bounds the working set whatever N is."""
    n = ints.shape[0]
    n_chunks = n // chunk
    live = jnp.isfinite(norms)

    def step(acc, xs):
        ii, ff, lv = xs
        mask = F.eval_program_batched(programs, ii, ff, xp=jnp) & lv[None, :]
        return acc + jnp.sum(mask, axis=1, dtype=jnp.int32), None

    b = programs["valid"].shape[0]
    count, _ = jax.lax.scan(
        step, jnp.zeros((b,), jnp.int32),
        (ints.reshape(n_chunks, chunk, ints.shape[1]),
         floats.reshape(n_chunks, chunk, floats.shape[1]),
         live.reshape(n_chunks, chunk)))
    total = jnp.maximum(jnp.sum(live, dtype=jnp.int32), 1)
    return count.astype(jnp.float32) / total.astype(jnp.float32)


def check_band(p_hat: np.ndarray, n: int, total: int,
               lam: float) -> np.ndarray:
    """(B,) True where ``p_hat >= lam`` yet Eq. 1's bound at ``CHECK_Z``
    cannot rule out a true selectivity under ``lam``: the requests whose
    route an exact count must decide.  ``n`` sample rows drawn from
    ``total``; no sample, no band."""
    p = np.asarray(p_hat, np.float64)
    if n <= 0 or total <= 0:
        return np.zeros(p.shape, bool)
    with np.errstate(invalid="ignore"):     # p = 0: 0 * inf
        sigma = p * relative_error(n, p, total)
        return (p >= lam) & (p - CHECK_Z * sigma < lam)


def route(p_hat: np.ndarray, lam: float,
          p_exact: np.ndarray | None = None) -> np.ndarray:
    """True -> PreFBF (brute force); False -> FAVOR graph search.
    ``p_exact`` holds the exact selectivity of the checked requests and NaN
    elsewhere: where it is a number, it decides the route."""
    brute = np.asarray(p_hat) < lam
    if p_exact is None:
        return brute
    p_exact = np.asarray(p_exact)
    checked = ~np.isnan(p_exact)
    return np.where(checked, p_exact < lam, brute)
