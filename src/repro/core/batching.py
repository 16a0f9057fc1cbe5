"""Shape-stable sub-batch execution: bucket padding from router to kernels.

The selector (paper section 4.1) splits every serve batch into graph/brute
sub-batches whose sizes are *data dependent* -- each new ``(route, size)``
pair used to trigger a fresh XLA/Pallas compile, which is exactly the
serving-p99 spike the filtered-ANNS system studies attribute to
mixed-selectivity traffic.  This module pins every compiled entry point to a
small, fixed set of power-of-two bucket shapes:

  BatchSpec      -- frozen policy: pow-2 bucket sizes between ``min_bucket``
                    and ``max_bucket`` plus the pad-row content policy.
                    Carried on ``SearchOptions.batch``; ``None`` disables
                    padding (the pre-1.2 behavior).
  pad_to_bucket  -- pad queries + stacked filter programs (+ optional p_hat)
                    up to the bucket size.  Pad rows carry an ALWAYS-FALSE
                    filter program (no disjunct live, infeasible intervals)
                    and a False entry in the returned validity mask, so they
                    match nothing and every backend/kernel can drop them
                    without touching real rows -- results stay bit-identical
                    to the unpadded path.
  unpad          -- strip the pad rows off result arrays.
  ShapeRegistry  -- per-engine ledger of the distinct shapes that reached a
                    compiled entry point (compile events) and of the padding
                    overhead actually paid.
  warmup         -- explicitly drive every (route, bucket) executable once
                    with an all-pad batch, so first-request traffic never
                    pays a compile.

Everything here is host-side policy: the device-side contract is only the
``valid`` mask that ``Backend.search_graph``/``search_brute`` and the
filtered_topk / gather_distance / pq_adc kernel ops accept.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np

from . import filters as F

PAD_POLICIES = ("zero", "repeat")


def _is_pow2(n: int) -> bool:
    return n >= 1 and (n & (n - 1)) == 0


@dataclass(frozen=True)
class BatchSpec:
    """Frozen bucket-padding policy for one engine/options instance.

    min_bucket/max_bucket bound the pow-2 bucket set (both must themselves
    be powers of two); batches above ``max_bucket`` round up to a multiple
    of it (the engine's ``max_batch`` normally caps them first).
    ``pad_policy`` picks the pad *query* rows: "zero" rows (default) or
    "repeat" of the last real row -- pad *filter* rows are always the
    always-false program, so the choice never affects results.
    """
    min_bucket: int = 8
    max_bucket: int = 512
    pad_policy: str = "zero"

    def __post_init__(self):
        for name in ("min_bucket", "max_bucket"):
            v = getattr(self, name)
            if not _is_pow2(v):
                raise ValueError(f"BatchSpec.{name} must be a power of two "
                                 f">= 1, got {v}")
        if self.min_bucket > self.max_bucket:
            raise ValueError(f"BatchSpec.min_bucket ({self.min_bucket}) must "
                             f"be <= max_bucket ({self.max_bucket})")
        if self.pad_policy not in PAD_POLICIES:
            raise ValueError(f"BatchSpec.pad_policy must be one of "
                             f"{PAD_POLICIES}, got {self.pad_policy!r}")

    def buckets(self) -> tuple[int, ...]:
        """The full bucket ladder, min_bucket, 2*min_bucket, ..., max_bucket."""
        out = []
        b = self.min_bucket
        while b <= self.max_bucket:
            out.append(b)
            b *= 2
        return tuple(out)

    def bucket_for(self, n: int) -> int:
        """Smallest bucket >= n (above max_bucket: next multiple of it)."""
        if n < 1:
            raise ValueError(f"bucket_for needs n >= 1, got {n}")
        for b in self.buckets():
            if n <= b:
                return b
        return -(-n // self.max_bucket) * self.max_bucket


def false_program_rows(programs: dict, pad: int) -> dict:
    """``pad`` always-false stacked program rows shaped like ``programs``.

    No disjunct is live (valid == 0) and the interval constraints are
    infeasible (flo=+inf > fhi=-inf), matching compile_filter's dead-row
    convention, so the rows match no DB row under any evaluator.  Host-side
    sidecar keys riding on the dict (the per-row tenant ``scope`` the cache
    subsystem consumes) are zero-padded: scope 0 is the unscoped default.
    """
    fills = {"flo": jnp.inf, "fhi": -jnp.inf}
    out = {}
    for k, v in programs.items():
        v = jnp.asarray(v)
        shape = (pad,) + tuple(v.shape[1:])
        fill = fills.get(k)
        out[k] = (jnp.zeros(shape, v.dtype) if fill is None
                  else jnp.full(shape, fill, v.dtype))
    return out


def pad_programs(spec: BatchSpec, programs: dict):
    """Pad a stacked program dict alone to its bucket.

    Returns ``(programs, valid)`` with ``valid`` a host (bucket,) bool mask
    that is True exactly on the original rows.
    """
    n = int(np.asarray(programs["valid"]).shape[0])
    bucket = spec.bucket_for(n)
    valid = np.arange(bucket) < n
    if bucket == n:
        return programs, valid
    pad_rows = false_program_rows(programs, bucket - n)
    programs = {k: jnp.concatenate([jnp.asarray(v), pad_rows[k]])
                for k, v in programs.items()}
    return programs, valid


def pad_to_bucket(spec: BatchSpec, queries, programs: dict, p_hat=None):
    """Pad one sub-batch up to its bucket size.

    queries (n, d) and the stacked program dict gain ``bucket - n`` pad rows
    (always-false programs; query content per ``spec.pad_policy``); the
    optional per-query ``p_hat`` is zero-padded.  Returns
    ``(queries, programs, p_hat, valid)``; strip results with ``unpad``.
    """
    queries = jnp.asarray(queries)
    n = int(queries.shape[0])
    bucket = spec.bucket_for(n)
    valid = np.arange(bucket) < n
    if bucket == n:
        return queries, programs, p_hat, valid
    pad = bucket - n
    if spec.pad_policy == "repeat":
        qpad = jnp.repeat(queries[-1:], pad, axis=0)
    else:
        qpad = jnp.zeros((pad,) + tuple(queries.shape[1:]), queries.dtype)
    queries = jnp.concatenate([queries, qpad])
    pad_rows = false_program_rows(programs, pad)
    programs = {k: jnp.concatenate([jnp.asarray(v), pad_rows[k]])
                for k, v in programs.items()}
    if p_hat is not None:
        p_hat = np.concatenate([np.asarray(p_hat, np.float32),
                                np.zeros((pad,), np.float32)])
    return queries, programs, p_hat, valid


def unpad(n: int, *arrays):
    """Strip pad rows: slice every array back to its first ``n`` rows."""
    out = tuple(a[:n] for a in arrays)
    return out[0] if len(out) == 1 else out


# ---------------------------------------------------------------------------
# Compiled-shape accounting
# ---------------------------------------------------------------------------
def route_key(kind: str, opts) -> tuple:
    """The jit-static identity of one backend entry point: shapes recorded
    under different keys correspond to genuinely different executables."""
    if opts is None or kind in ("estimate", "check"):
        return ()  # the selectivity executables are SearchConfig-independent
    cfg = opts.search_config()
    if kind == "brute":
        return (cfg, opts.use_pq, opts.rerank)
    return (cfg,)


class ShapeRegistry:
    """Ledger of distinct (kind, batch-shape, static-config) triples that
    reached a compiled backend entry point, plus the padding overhead paid.

    A *new* triple is a compile event (XLA/Pallas trace + compile); repeat
    triples reuse the cached executable.  ``ServeEngine`` owns one registry
    and surfaces ``stats()`` to operators; the smoke benchmark asserts the
    per-kind shape count stays bounded by the bucket-ladder length.
    """

    def __init__(self):
        self._shapes: dict[tuple, int] = {}
        self.compile_events = 0
        self.pad_rows = 0
        self.real_rows = 0

    def record(self, kind: str, size: int, real: int, opts=None) -> bool:
        """Note one backend call; True when its shape is new (a compile)."""
        key = (kind, int(size)) + route_key(kind, opts)
        new = key not in self._shapes
        self._shapes[key] = self._shapes.get(key, 0) + 1
        if new:
            self.compile_events += 1
        self.pad_rows += int(size) - int(real)
        self.real_rows += int(real)
        return new

    @property
    def compiled_shapes(self) -> int:
        return len(self._shapes)

    def sizes_by_kind(self) -> dict[str, tuple[int, ...]]:
        """kind -> sorted distinct batch sizes seen (the compile guard)."""
        out: dict[str, set] = {}
        for (kind, size, *_rest) in self._shapes:
            out.setdefault(kind, set()).add(size)
        return {k: tuple(sorted(v)) for k, v in out.items()}

    def reset_rows(self) -> None:
        """Zero the pad/real row counters (the shape set -- which mirrors
        still-live compiled executables -- survives)."""
        self.pad_rows = 0
        self.real_rows = 0

    def stats(self) -> dict:
        total = self.pad_rows + self.real_rows
        return {
            "compiled_shapes": self.compiled_shapes,
            "compile_events": self.compile_events,
            "calls": sum(self._shapes.values()),
            "pad_rows": self.pad_rows,
            "real_rows": self.real_rows,
            "pad_overhead": self.pad_rows / total if total else 0.0,
            "sizes": self.sizes_by_kind(),
        }


def record(registry, kind: str, size: int, real: int, opts=None) -> None:
    """Registry-optional convenience used by router.execute / warmup."""
    if registry is not None:
        registry.record(kind, size, real, opts)


# ---------------------------------------------------------------------------
# Explicit warm-up
# ---------------------------------------------------------------------------
def warmup(backend, opts, *, buckets=None, registry=None) -> tuple[int, ...]:
    """Compile every (estimate / check / graph / brute, bucket) executable
    now (the selector's exact ``check`` only where the backend has one and
    no route is forced).

    Drives the innermost backend (cache decorators are unwrapped -- their
    host-side layers never compile) with all-pad batches: zero queries,
    always-false programs, an all-False validity mask and p_hat = 0, i.e.
    exactly the shapes + static config live traffic will hit once
    ``opts.batch`` bucket-pads the sub-batches.  Returns the bucket ladder
    warmed.  Graph lanes with a False mask never expand, so warm-up cost is
    compile time, not search time.

    ``opts.batch`` must be set: without it, live traffic runs raw
    data-dependent shapes with no validity mask -- a different jit
    signature per batch -- so nothing warmed here would ever be reused and
    the compile cost would buy nothing.  Routes excluded by ``opts.force``
    are skipped (a pinned-brute engine never dispatches graph executables).
    """
    if opts.batch is None:
        raise ValueError(
            "warmup() needs SearchOptions.batch set: unpadded traffic runs "
            "raw data-dependent shapes that never match the warmed "
            "executables (pass batch=BatchSpec(...) on the engine options)")
    spec = opts.batch
    if buckets is None:
        bucket_list = spec.buckets()
    else:
        bucket_list = tuple(int(b) for b in buckets)
    target = backend
    inner = getattr(target, "inner", None)
    while inner is not None:
        target, inner = inner, getattr(inner, "inner", None)
    dim = int(target.dim)
    for b in bucket_list:
        queries = jnp.zeros((b, dim), jnp.float32)
        progs = {k: jnp.asarray(v) for k, v in F.compile_batch(
            [F.FalseFilter()] * b, target.schema).items()}
        valid = np.zeros((b,), bool)
        record(registry, "estimate", b, 0)
        np.asarray(target.estimate(progs))
        check = getattr(target, "exact_selectivity", None)
        if opts.force is None and check is not None:
            record(registry, "check", b, 0)
            np.asarray(check(progs, valid=valid))
        if opts.force != "brute":
            record(registry, "graph", b, 0, opts)
            out = target.search_graph(queries, progs,
                                      jnp.zeros((b,), jnp.float32), opts,
                                      valid=valid)
            np.asarray(out["ids"])
        if opts.force != "graph":
            record(registry, "brute", b, 0, opts)
            bid, _ = target.search_brute(queries, progs, opts, valid=valid)
            np.asarray(bid)
    return bucket_list
