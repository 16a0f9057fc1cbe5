"""Pluggable distance scorers for the graph traversal (one protocol, three
memory formats).

FAVOR's exclusion-distance mechanism (Eq. 2) is scorer-agnostic: it reshapes
*whatever* distance distribution the traversal sees.  The traversal loop in
``core.search`` therefore composes three orthogonal pieces per neighbor
block:

    side rows   -> (B, M, C) int32       (``side_table``: norm + attributes)
    score_block -> (B, M) distances      (this module: f32 / PQ-ADC / SQ)
    filter eval -> (B, M) TD mask        (the Pallas exact kernel's own, else
                                          filters.eval_program_gathered)
    exclusion   -> dbar = d + (1-td)*D   (``exclusion_compose`` below)

A Scorer is a *frozen, array-free* dataclass so it can ride along as a
jit-static parameter (it is derived from the jit-static ``SearchConfig`` via
``scorer_for``); all device state lives in the ``g`` array dict and in the
per-query ``state`` dict built once by ``prepare`` before the while_loop:

    prepare(g, queries, programs)    -> state   # e.g. the ADC LUTs (B, M, K)
    score_block(g, state, ids, side) -> (d, td) # (B, M) f32 distances for
                                                # the gathered ids, and the
                                                # TD mask or None

``programs`` is threaded through ``prepare`` only so the Pallas exact path
can reuse the fused gather_distance kernel, which evaluates the filter
in-kernel: that scorer returns the block's TD mask, and the traversal
evaluates the filter itself, on the same side rows, only where a scorer
returns None.  Which of the two a scorer does is fixed by the scorer.

Scorers return *distance-scale* values (sqrt of the squared forms) so the
exclusion distance D -- calibrated in true-distance units from Delta_d --
composes identically whichever scorer runs.  Quantized scorers are
approximate: the traversal re-ranks their final TD candidates with the same
exact float32 pass the brute route uses (``quant.adc._exact_rerank``).

Bandwidth accounting: ``bytes_per_row`` is what one gathered neighbor row
streams from HBM -- 4*d for f32, M codes for PQ, d codes for SQ -- the
``bench_qps_recall --smoke`` sweep reports the per-hop reduction.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Protocol, runtime_checkable

import jax
import jax.numpy as jnp

GRAPH_QUANT_KINDS = (None, "pq", "sq")


def pairwise_dist(q: jnp.ndarray, vecs: jnp.ndarray,
                  vnorm: jnp.ndarray) -> jnp.ndarray:
    """(B, d), (B, M, d), (B, M) -> true Euclidean distance (B, M).

    The dot is a *batched mat-vec* (one d-contraction per (b, m) pair), so
    it is written as multiply + last-axis reduce rather than an einsum:
    XLA lowers the reduce with a batch-size-independent accumulation order,
    which keeps results bit-identical when bucket padding changes B (a
    dot_general here picks different codegen for B=1 vs B=8 on CPU).  The
    contraction never fed the MXU efficiently anyway -- b is a batch dim.
    """
    qn = jnp.sum(q * q, axis=-1)  # (B,)
    dot = jnp.sum(q[:, None, :] * vecs, axis=-1)
    d2 = vnorm + qn[:, None] - 2.0 * dot
    return jnp.sqrt(jnp.maximum(d2, 0.0))


def side_table(g: dict) -> jnp.ndarray:
    """(N, C) int32: each node's per-row scalars packed into one row --
    its norm, its int attributes, its float attributes and, once the index
    has tombstones, its ``alive`` flag -- with floats bitcast, so every
    value comes back bit for bit.  A wave reads a neighbour block's scalars
    as one row gather of this rank-2 table (a gather from a 1-D array is
    several times slower per row on TPU)."""
    cols = [jax.lax.bitcast_convert_type(
                g["norms"].astype(jnp.float32), jnp.int32)[:, None],
            g["attrs_int"].astype(jnp.int32),
            jax.lax.bitcast_convert_type(
                g["attrs_float"].astype(jnp.float32), jnp.int32)]
    if "alive" in g:
        cols.append(g["alive"].astype(jnp.int32)[:, None])
    return jnp.concatenate(cols, axis=1)


def side_fields(g: dict, rows: jnp.ndarray):
    """Gathered side rows (..., C) -> (norms (...) f32, ints (..., mi),
    floats (..., mf) f32, alive (...) bool or None), as ``side_table``
    packed them from ``g``."""
    mi, mf = g["attrs_int"].shape[1], g["attrs_float"].shape[1]
    norms = jax.lax.bitcast_convert_type(rows[..., 0], jnp.float32)
    ints = rows[..., 1:1 + mi]
    floats = jax.lax.bitcast_convert_type(rows[..., 1 + mi:1 + mi + mf],
                                          jnp.float32)
    alive = rows[..., 1 + mi + mf] > 0 if "alive" in g else None
    return norms, ints, floats, alive


def exclusion_compose(d: jnp.ndarray, td: jnp.ndarray,
                      D: jnp.ndarray) -> jnp.ndarray:
    """Eq. 2: adjusted distance ``d + D`` for non-target rows, ``d`` for TD.

    Order-preserving within each class: for two TD rows (or two non-TD
    rows) the composition adds the same constant, so their relative order
    under any scorer is unchanged -- the property test in test_scoring
    checks exactly this.
    """
    return d + jnp.where(td, 0.0, D)


@runtime_checkable
class Scorer(Protocol):
    """Distance scorer contract consumed by the unified traversal."""

    kind: str    # "exact" | "pq" | "sq" -- the SearchOptions.graph_quant name
    exact: bool  # True -> score_block returns true f32 distances (no re-rank)
    # optional ``shared_state``: names of prepare() keys that are
    # query-independent (no leading batch axis); the lane-compaction ladder
    # slices every other state leaf per stage and must leave these alone

    def required_keys(self) -> tuple[str, ...]:
        """g-dict arrays this scorer reads (validation happens host-side)."""
        ...

    def prepare(self, g: dict, queries, programs: dict) -> dict:
        """Per-query device state built once before the traversal loop."""
        ...

    def score_block(self, g: dict, state: dict, ids, side=None) -> tuple:
        """(d, td) for the gathered DB rows ``ids`` (clamped >= 0; masking
        of pad/visited entries stays in the traversal): (B, M) distances,
        and the block's (B, M) TD mask under the batch's filter programs,
        or None where the scorer does not evaluate the filter.  ``side``
        holds ``side_table(g)[ids]``; None gathers it here."""
        ...

    def bytes_per_row(self, g: dict) -> int:
        """Bytes one gathered neighbor row streams from HBM."""
        ...


@dataclass(frozen=True)
class ExactScorer:
    """Full-precision float32 scoring (the seed behavior).

    ``use_pallas=True`` routes each neighbor block through the
    kernels/gather_distance kernel instead of the jnp gather + mul/reduce,
    and returns the TD mask the kernel evaluates beside the distances.
    Both read the block's norms (and the kernel its attributes) from the
    gathered side rows.
    """
    use_pallas: bool = False
    kind = "exact"
    exact = True

    def required_keys(self) -> tuple[str, ...]:
        return ("vectors", "norms")

    def prepare(self, g: dict, queries, programs: dict) -> dict:
        state = {"q": jnp.asarray(queries)}
        if self.use_pallas:
            state["programs"] = programs
        return state

    def score_block(self, g: dict, state: dict, ids, side=None) -> tuple:
        if side is None:
            side = side_table(g)[ids]
        norms, ints, floats, _ = side_fields(g, side)
        if self.use_pallas:
            from ..kernels.gather_distance import ops as gd_ops
            # dvec=0 -> plain distances; the traversal owns the exclusion
            # composition, on the kernel's TD mask
            d, td = gd_ops.gather_distance(
                g["vectors"], norms, ints, floats, state["q"], ids,
                state["programs"],
                jnp.zeros((state["q"].shape[0],), jnp.float32))
            return jnp.minimum(d, 3.0e38), td  # keep +inf out of the pools
        return pairwise_dist(state["q"], g["vectors"][ids], norms), None

    def bytes_per_row(self, g: dict) -> int:
        return 4 * int(g["vectors"].shape[1])


@dataclass(frozen=True)
class PqAdcScorer:
    """Compressed scoring: per-query ADC LUTs + gathered uint8 codes.

    ``prepare`` builds the (B, M, K) squared-subdistance tables once
    (quant.adc.build_luts) and stores them **bfloat16** by default, halving
    the per-query LUT state; every lookup widens back to float32 before the
    subspace accumulation, so only the table entries themselves are rounded
    (~3 significant digits -- noise next to the PQ quantization error, and
    the traversal's final candidates get an exact f32 re-rank regardless).
    Each neighbor block is then M table lookups + adds per row, through ONE
    flat (B, M*K) gather -- the gathered-row traffic drops from 4*d to M
    bytes.  ``use_pallas=True`` runs the row-batched block-gather ADC kernel
    (kernels/pq_adc.pq_adc_gather) instead of the jnp take_along_axis.
    """
    use_pallas: bool = False
    lut_bf16: bool = True
    kind = "pq"
    exact = False

    def required_keys(self) -> tuple[str, ...]:
        return ("codes", "centroids")

    def prepare(self, g: dict, queries, programs: dict) -> dict:
        from ..quant.adc import build_luts
        luts = build_luts(g["centroids"], jnp.asarray(queries))
        if self.lut_bf16:
            luts = luts.astype(jnp.bfloat16)
        return {"luts": luts}

    def score_block(self, g: dict, state: dict, ids, side=None) -> tuple:
        luts = state["luts"]
        if self.use_pallas:
            from ..kernels.pq_adc import ops as pq_ops
            adc2 = pq_ops.pq_adc_gather(g["codes"], luts, ids)
        else:
            b, m, k = luts.shape
            codes = g["codes"][ids].astype(jnp.int32)        # (B, M0, m)
            # ONE flat jnp.take against the fully flattened (B*M*K) table:
            # row b / subspace mm / code c addresses entry (b*M + mm)*K + c.
            # Globalizing the row index lets XLA lower a single 1-d gather
            # (~2.5x faster on CPU than the per-batch take_along_axis or the
            # former 4-d broadcast gather).  Indices are stage-local, so
            # lane compaction's sliced LUTs line up row for row.
            gidx = ((jnp.arange(b, dtype=jnp.int32)[:, None, None] * m
                     + jnp.arange(m, dtype=jnp.int32)[None, None, :]) * k
                    + codes)
            gath = jnp.take(luts.reshape(-1), gidx)
            adc2 = jnp.sum(gath.astype(jnp.float32), axis=-1)  # f32 accum
        # sqrt: ADC tables are squared sub-distances; the exclusion D and
        # the termination test live in true-distance units
        return jnp.sqrt(jnp.maximum(adc2, 0.0)), None

    def bytes_per_row(self, g: dict) -> int:
        return int(g["codes"].shape[1])

    def lut_bytes(self, g: dict, batch: int) -> int:
        m, k = int(g["centroids"].shape[0]), int(g["centroids"].shape[1])
        return (2 if self.lut_bf16 else 4) * batch * m * k


@dataclass(frozen=True)
class SqScorer:
    """Scalar-quantization scoring: gathered int8 codes contracted against
    folded affine weights (4x fewer bytes than f32; exact when the corpus
    lies on the int8 grid, which the lossless bit-parity test exploits).

    With x = c*s + lo (per-dim scale/offset) the squared distance folds to

        d2 = sum_j c_j^2 s_j^2                      (query-independent)
           + sum_j c_j * (2 s_j lo_j - 2 q_j s_j)   (per-query linear)
           + ||lo||^2 + ||q||^2 - 2 q.lo            (per-query constant)

    so ``prepare`` bakes the three weight groups once per batch and
    ``score_block`` touches the gathered codes exactly once -- no (B, M, d)
    dequantized copy, no recomputed row norms.  The quadratic term is ONE
    2-d ``dot_general`` with ``preferred_element_type=f32`` (on TPU that is
    the low-precision-in / f32-accumulate MXU shape; gemv on CPU); the
    per-query linear term is a multiply + last-axis reduce, NOT a batched
    dot, for the bucket-size bit-stability ``pairwise_dist`` documents --
    lane compaction re-invokes the scorer at every stage width, so
    distances must not depend on the leading batch dimension.
    """
    kind = "sq"
    exact = False
    # w2 is query-independent (d, 1) -- exempt from lane-compaction slicing
    shared_state = ("w2",)

    def required_keys(self) -> tuple[str, ...]:
        return ("codes", "sq_lo", "sq_scale")

    def prepare(self, g: dict, queries, programs: dict) -> dict:
        q = jnp.asarray(queries)
        s, lo = g["sq_scale"], g["sq_lo"]
        qn = jnp.sum(q * q, axis=-1)                          # (B,)
        return {
            "w2": (s * s)[:, None],                           # (d, 1)
            "w_lin": 2.0 * s[None, :] * (lo[None, :] - q),    # (B, d)
            # mul+reduce (not q @ lo): bit-stable across bucket widths
            "const": jnp.sum(lo * lo) + qn
                     - 2.0 * jnp.sum(q * lo[None, :], axis=-1),
        }

    def score_block(self, g: dict, state: dict, ids, side=None) -> tuple:
        c = g["codes"][ids].astype(jnp.float32)               # (B, M, d)
        b, m0, d = c.shape
        quad = jax.lax.dot_general(
            (c * c).reshape(b * m0, d), state["w2"],
            (((1,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32).reshape(b, m0)
        lin = jnp.sum(c * state["w_lin"][:, None, :], axis=-1)
        d2 = quad + lin + state["const"][:, None]
        return jnp.sqrt(jnp.maximum(d2, 0.0)), None

    def bytes_per_row(self, g: dict) -> int:
        return int(g["codes"].shape[1])


def scorer_for(cfg) -> Scorer:
    """The Scorer implied by a jit-static SearchConfig (same cfg -> same
    scorer, so compiled-executable caches keyed on cfg stay sound)."""
    if cfg.graph_quant == "pq":
        return PqAdcScorer(use_pallas=cfg.use_pallas)
    if cfg.graph_quant == "sq":
        return SqScorer()
    if cfg.graph_quant is not None:
        raise ValueError(f"graph_quant must be one of {GRAPH_QUANT_KINDS}, "
                         f"got {cfg.graph_quant!r}")
    return ExactScorer(use_pallas=cfg.use_pallas)
