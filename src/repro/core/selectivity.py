"""Selectivity estimation by random sampling (paper Section 4.2).

The estimator draws a fixed random subset of the dataset once at index-build
time (so the sampled attribute rows are a small dense block that stays hot in
VMEM/cache) and, per query, evaluates the compiled filter program over the
sample: ``p_hat = mean(mask)``.

Because the number of target points in a sample without replacement follows a
hyper-geometric distribution, the relative error of ``p_hat`` is (Eq. 1)

    rel_err = sqrt((1-p) / (n p) * (1 - n/N))

At a 1% sample of a million rows (n = 10,000) that is about 1% at p = 50%,
3% at p = 10% and 9.9% at p = 1%; at the 328-row sample of 2^15 rows it is
55% at p = 1%.  So near the routing threshold lambda the estimate alone
cannot tell a filter just under lambda from one just over it: a 0.5% filter
reads 1% or more in about one sample in twelve at n = 328.  Error there is
not harmless -- a sub-lambda filter sent to the graph loses most of its
recall -- which is why the selector confirms estimates near lambda with an
exact count (selector.py).
"""
from __future__ import annotations

import numpy as np

from . import filters as F


def sample_indices(n: int, rate: float = 0.01, min_size: int = 256,
                   max_size: int = 65536, seed: int = 0) -> np.ndarray:
    """Fixed sample drawn once at build time (without replacement)."""
    size = int(np.clip(int(round(n * rate)), min(min_size, n), min(max_size, n)))
    rng = np.random.default_rng(seed)
    return np.sort(rng.choice(n, size=size, replace=False)).astype(np.int32)


def relative_error(n: int, p, total: int):
    """Eq. 1: hyper-geometric relative error of the sampled estimate
    (inf at p = 0).  ``p`` may be a scalar (returns a float) or an array."""
    p_arr = np.asarray(p, np.float64)
    with np.errstate(divide="ignore", invalid="ignore"):
        err = np.sqrt((1.0 - p_arr) / (n * p_arr)
                      * max(0.0, 1.0 - n / total))
    err = np.where(p_arr > 0.0, err, np.inf)
    return float(err) if err.ndim == 0 else err


def estimate_selectivity(program, sample_ints, sample_floats, xp=np):
    """p_hat for a single compiled program over the pre-drawn sample rows."""
    mask = F.eval_program(program, sample_ints, sample_floats, xp=xp)
    return mask.mean(dtype=sample_floats.dtype if xp is not np else np.float64)


def estimate_selectivity_batched(programs, sample_ints, sample_floats, xp=np):
    """(B,) p_hat for batched programs.  Pure ufunc math: works as numpy or
    traced jax (the distributed selector psum-averages per-shard results)."""
    mask = F.eval_program_batched(programs, sample_ints, sample_floats, xp=xp)
    return mask.mean(axis=1)


def exact_selectivity(program, attrs: "F.AttributeTable") -> float:
    """Ground-truth p by full scan (tests / benchmarks only)."""
    mask = F.eval_program(program, attrs.ints, attrs.floats)
    return float(mask.mean())
