"""Sharded FAVOR serving across the production mesh (DESIGN.md section 4).

Layout (classic distributed-ANNS segment model, Milvus/Vearch style):
 * the DB (vectors, attributes, per-shard HNSW subgraphs, selectivity sample)
   is sharded on the ``model`` axis: shard s owns rows [s*Ns, (s+1)*Ns);
 * the query batch is sharded on (``pod``, ``data``) -- pure data parallelism;
 * every (data, model) mesh cell runs the single-shard search from search.py
   on its query block x DB shard, then local top-k are ``all_gather``-ed along
   ``model`` and sort-merged (k per shard -> k global; tiny collective);
 * selectivity estimation psum-combines per-shard sample counts so every
   shard computes the same p_hat and takes the same route deterministically.

Each shard has its own HNSW (built independently offline -- embarrassingly
parallel build, linear scaling in shards), its own entry point and its own
Delta_d; D is computed per shard from the *global* p_hat and the local
Delta_d, which matches the paper's global-statistic design per shard.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import exclusion
from . import filters as F
from . import prefbf, selectivity
from .hnsw import HnswIndex, HnswParams, build_hnsw
from .search import SearchConfig, favor_graph_search


def largest_divisor(n: int, cap: int) -> int:
    """Largest divisor of ``n`` that is <= ``cap`` (e.g. scan chunk sizes and
    mesh-axis extents that must evenly split a row count)."""
    d = max(1, min(cap, n))
    while n % d:
        d -= 1
    return d


# ---------------------------------------------------------------------------
# Sharded index container
# ---------------------------------------------------------------------------
def db_specs(model_axis: str = "model", quant: str | None = None,
             live: bool = False) -> dict:
    """Partition specs for the serve DB dict.

    ``quant`` extends the base layout with the compressed-scan arrays:
    "codes" rows are co-sharded with their vectors on ``model_axis``; the
    (tiny) codebook tables are replicated on every device.  ``live`` adds
    the tombstone mask ("alive", row-co-sharded) of a mutated backend.
    """
    sh = {
        "vectors": P(model_axis, None), "norms": P(model_axis),
        "neighbors0": P(model_axis, None), "upper": P(None, model_axis, None),
        "attrs_int": P(model_axis, None), "attrs_float": P(model_axis, None),
        "entry": P(model_axis), "delta_d": P(model_axis),
        "sample_int": P(model_axis, None), "sample_float": P(model_axis, None),
    }
    if live:
        sh["alive"] = P(model_axis)
    if quant is not None:
        sh["codes"] = P(model_axis, None)
        if quant == "pq":
            sh["centroids"] = P(None, None, None)
        elif quant == "sq":
            sh["sq_lo"] = P(None)
            sh["sq_scale"] = P(None)
        else:
            raise ValueError(f"quant must be 'pq', 'sq' or None, got {quant!r}")
    return sh


@dataclass
class ShardedFavorArrays:
    """Global-shaped arrays; axis 0 of every DB array is sharded on "model".

    vectors     (S*Ns, d)      norms      (S*Ns,)
    neighbors0  (S*Ns, M0)     upper      (L_up, S*Ns, M)   [local node ids]
    attrs_int   (S*Ns, m_i)    attrs_float(S*Ns, m_f)
    entry       (S,) int32     delta_d    (S,) f32
    sample_int  (S*ns, m_i)    sample_float (S*ns, m_f)

    With a codebook attached (attach_quant): codes (S*Ns, M) uint8 plus the
    replicated codebook tables (centroids | sq_lo/sq_scale).
    """
    arrays: dict
    n_shards: int
    shard_rows: int
    sample_rows: int  # per shard
    quant: str | None = None  # "pq" | "sq" once attach_quant has run

    def specs(self) -> dict:
        return db_specs(quant=self.quant)


def attach_quant(sharded: ShardedFavorArrays, codebook) -> ShardedFavorArrays:
    """Encode the sharded DB under ``codebook`` so the brute route can
    stream codes instead of float32 rows.  Row i's code lands on the same
    shard as vector i (contiguous row partition on "model")."""
    from .. import quant
    arrays = dict(sharded.arrays)
    arrays["codes"] = quant.encode(codebook, arrays["vectors"])
    if isinstance(codebook, quant.PQCodebook):
        kind = "pq"
        arrays["centroids"] = np.asarray(codebook.centroids, np.float32)
    else:
        kind = "sq"
        arrays["sq_lo"] = np.asarray(codebook.lo, np.float32)
        arrays["sq_scale"] = np.asarray(codebook.scale, np.float32)
    return ShardedFavorArrays(arrays, sharded.n_shards, sharded.shard_rows,
                              sharded.sample_rows, quant=kind)


def build_sharded(vectors: np.ndarray, attrs: F.AttributeTable, n_shards: int,
                  params: HnswParams | None = None, sample_rate: float = 0.01,
                  seed: int = 0, min_sample: int = 8,
                  max_sample: int = 65536,
                  build_fn=None, n_valid: int | None = None,
                  keep_parts: bool = False):
    """Partition rows round-robin-contiguously, build one HNSW per shard.

    ``min_sample``/``max_sample`` bound the TOTAL selectivity-sample size
    (split evenly across shards) exactly like SelectorConfig bounds the
    single-host sample, so the psum-combined p_hat matches the single-host
    estimator's variance and both backends take the same routes -- and the
    per-batch jitted estimate stays O(max_sample) however large the DB.

    ``build_fn(vectors, params) -> HnswIndex`` overrides the per-shard build
    (default sequential ``build_hnsw``; pass ``index.bulk.build_hnsw_bulk``
    for the device-parallel wave pipeline).

    ``n_valid`` marks rows >= n_valid as permanently-dead headroom: they are
    excluded from the per-shard graph build (their neighbor rows stay -1, so
    a later incremental merge can register real rows onto those positions)
    and from the selectivity sample.  The headroom convention requires the
    dead tail to live inside the LAST shard; a fully-dead shard falls back
    to the legacy zero-vector build so its entry/delta_d stay defined.

    ``keep_parts=True`` additionally returns the per-shard HnswIndex objects
    (the handles an incremental merge grows via ``bulk_add``)."""
    n = vectors.shape[0]
    assert n % n_shards == 0, "row count must divide the model axis"
    build_fn = build_fn or build_hnsw
    ns = n // n_shards
    n_valid = n if n_valid is None else int(n_valid)
    parts = []
    lvs = []
    max_lup = 0
    for s in range(n_shards):
        sl = slice(s * ns, (s + 1) * ns)
        p = params or HnswParams()
        p = HnswParams(M=p.M, M0=p.M0, efc=p.efc, ml=p.ml, alpha=p.alpha,
                       heuristic=p.heuristic, seed=p.seed + s)
        lv = min(ns, n_valid - s * ns)
        lv = ns if lv < 1 else lv
        idx = build_fn(vectors[sl][:lv], p)
        parts.append((idx, sl))
        lvs.append(lv)
        max_lup = max(max_lup, len(idx.levels) - 1)

    sample_n = max(8, -(-min_sample // n_shards), int(round(ns * sample_rate)))
    sample_n = min(sample_n, ns, max(8, max_sample // n_shards))
    rng = np.random.default_rng(seed + 31)

    neighbors0 = np.full((n, parts[0][0].params.M0), -1, np.int32)
    upper = np.full((max_lup, n, parts[0][0].params.M), -1, np.int32)
    entry = np.zeros((n_shards,), np.int32)
    delta_d = np.zeros((n_shards,), np.float32)
    s_int = np.zeros((n_shards * sample_n, attrs.ints.shape[1]), np.int32)
    s_flt = np.zeros((n_shards * sample_n, attrs.floats.shape[1]), np.float32)
    norms = np.einsum("nd,nd->n", vectors, vectors).astype(np.float32)

    for s, (idx, sl) in enumerate(parts):
        lo, lv = sl.start, lvs[s]
        neighbors0[lo:lo + idx.n] = idx.levels[0]
        for li, lvl in enumerate(idx.levels[1:]):
            upper[li, lo:lo + idx.n] = lvl
        entry[s] = idx.entry_point
        delta_d[s] = idx.delta_d
        samp = rng.choice(lv, size=sample_n, replace=sample_n > lv) + lo
        s_int[s * sample_n:(s + 1) * sample_n] = attrs.ints[samp]
        s_flt[s * sample_n:(s + 1) * sample_n] = attrs.floats[samp]

    arrays = {
        "vectors": vectors.astype(np.float32), "norms": norms,
        "neighbors0": neighbors0, "upper": upper,
        "attrs_int": attrs.ints, "attrs_float": attrs.floats,
        "entry": entry, "delta_d": delta_d,
        "sample_int": s_int, "sample_float": s_flt,
    }
    sharded = ShardedFavorArrays(arrays, n_shards, ns, sample_n)
    if keep_parts:
        return sharded, [idx for idx, _ in parts]
    return sharded


def input_specs(n: int, dim: int, m_i: int, m_f: int, n_shards: int, *,
                m0: int = 32, m: int = 16, n_upper: int = 3,
                sample_rate: float = 0.01, width: int = 8,
                batch: int = 4096, dtype=jnp.float32) -> dict:
    """ShapeDtypeStruct stand-ins for the dry-run (no allocation)."""
    ns = n // n_shards
    sample_n = max(8, int(round(ns * sample_rate)))
    f32, i32 = dtype, jnp.int32
    sds = jax.ShapeDtypeStruct
    return {
        "db": {
            "vectors": sds((n, dim), f32), "norms": sds((n,), f32),
            "neighbors0": sds((n, m0), i32), "upper": sds((n_upper, n, m), i32),
            "attrs_int": sds((n, m_i), i32), "attrs_float": sds((n, m_f), f32),
            "entry": sds((n_shards,), i32), "delta_d": sds((n_shards,), jnp.float32),
            "sample_int": sds((n_shards * sample_n, m_i), i32),
            "sample_float": sds((n_shards * sample_n, m_f), f32),
        },
        "queries": sds((batch, dim), f32),
        "programs": {
            "valid": sds((batch, width), jnp.float32),
            "imask": sds((batch, width, m_i), jnp.uint32),
            "flo": sds((batch, width, m_f), f32),
            "fhi": sds((batch, width, m_f), f32),
        },
        "valid": sds((batch,), jnp.bool_),
    }


# ---------------------------------------------------------------------------
# Sharded serve steps
# ---------------------------------------------------------------------------
def _merge_topk(local_d, local_i, k: int, axis: str):
    """all_gather local (B, k) results along ``axis`` and sort-merge."""
    gd = jax.lax.all_gather(local_d, axis)          # (S, B, k)
    gi = jax.lax.all_gather(local_i, axis)
    s, b, _ = gd.shape
    gd = jnp.moveaxis(gd, 0, 1).reshape(b, s * k)
    gi = jnp.moveaxis(gi, 0, 1).reshape(b, s * k)
    order = jnp.argsort(gd, axis=1)[:, :k]
    return (jnp.take_along_axis(gd, order, axis=1),
            jnp.take_along_axis(gi, order, axis=1))


def make_serve_fns(mesh: Mesh, cfg: SearchConfig, *, ef_sel: int | None = None,
                   prefbf_chunk: int = 65536, query_axes=("data",),
                   model_axis: str = "model", quant: str | None = None,
                   rerank: int = 4, live: bool = False):
    """Build the jitted sharded serve steps for ``mesh``.

    Returns dict with:
      estimate(db, programs)                     -> (B,) p_hat (replicated)
      serve_graph(db, queries, programs, valid)  -> ids (B,k) GLOBAL ids, dists
      serve_brute(db, queries, programs, valid)  -> ids (B,k), dists
      serve_brute_pq(db, queries, programs, valid) [quant only] -> ids, dists

    ``valid`` is the (B,) bool row mask of the bucket-padding contract
    (core.batching): False rows are pad rows and come back as -1 / +inf
    (pass all-True when every row is real).

    With ``cfg.use_pallas`` the per-shard brute scans run through the
    filtered_topk / pq_adc Pallas kernels inside the shard_map body (each
    shard launches the kernel over its own row slice; the cross-shard top-k
    merge is unchanged).

    With ``quant`` set ("pq"/"sq") the db dict must carry the attach_quant
    arrays; serve_brute_pq streams only the uint8 codes per shard (ADC LUT
    scan, same DNF masking), exact-re-ranks the top ``rerank * k`` local
    candidates against the shard's float32 rows, and only then joins the
    cross-shard top-k merge -- so the bandwidth-bound scan never touches
    float32.

    With ``cfg.graph_quant`` set the *graph* route also scores on the
    attached codes (core.scoring): each shard's traversal gathers uint8
    code rows per hop and exact-re-ranks its final TD candidates before the
    cross-shard merge, so the per-hop neighbor fetch is code-resident too
    (requires ``quant`` == ``cfg.graph_quant``).
    """
    qspec = P(query_axes if len(query_axes) > 1 else query_axes[0], None)
    pspec_each = {"valid": P(qspec[0], None), "imask": P(qspec[0], None, None),
                  "flo": P(qspec[0], None, None), "fhi": P(qspec[0], None, None)}
    vspec = P(qspec[0])  # (B,) validity mask, co-sharded with the queries
    ef = ef_sel or cfg.ef
    dspecs = db_specs(model_axis, quant, live)

    def _scan_norms(db):
        """Per-shard norms for the brute scans: with a live DB, tombstoned
        rows take +inf (the padded-row convention) so they can never win."""
        if live:
            return jnp.where(db["alive"], db["norms"], jnp.inf)
        return db["norms"]

    # -- selectivity estimate (psum-combined; identical on all shards) -------
    def _estimate(db, programs):
        mask = F.eval_program_batched(
            programs, db["sample_int"], db["sample_float"], xp=jnp)  # (B, ns)
        cnt = jnp.sum(mask.astype(jnp.float32), axis=1)
        tot = jnp.asarray(mask.shape[1], jnp.float32)
        cnt = jax.lax.psum(cnt, model_axis)
        tot = jax.lax.psum(tot, model_axis)
        return cnt / tot

    estimate = jax.jit(jax.shard_map(
        _estimate, mesh=mesh,
        in_specs=(dspecs, pspec_each),
        out_specs=P(qspec[0]),
        check_vma=False))

    # -- graph route ----------------------------------------------------------
    if cfg.graph_quant is not None and cfg.graph_quant != quant:
        raise ValueError(
            f"cfg.graph_quant={cfg.graph_quant!r} needs the serve DB built "
            f"with matching attach_quant codes (quant={quant!r})")

    def _graph_from_phat(db, queries, programs, p_hat, valid):
        local_g = {
            "vectors": db["vectors"], "norms": db["norms"],
            "neighbors0": db["neighbors0"], "upper": db["upper"],
            "entry": db["entry"][0],
            "attrs_int": db["attrs_int"], "attrs_float": db["attrs_float"],
        }
        if live:
            local_g["alive"] = db["alive"]
        if cfg.graph_quant is not None:
            # scorer arrays (core.scoring): each shard scores its own code
            # rows; the replicated codebook tables ride along
            local_g["codes"] = db["codes"]
            if cfg.graph_quant == "pq":
                local_g["centroids"] = db["centroids"]
            else:
                local_g["sq_lo"] = db["sq_lo"]
                local_g["sq_scale"] = db["sq_scale"]
        D = exclusion.exclusion_distance(p_hat, ef, db["delta_d"][0],
                                         k=cfg.k, xp=jnp)
        out = favor_graph_search(local_g, queries, programs, D, cfg,
                                 valid=valid)
        shard = jax.lax.axis_index(model_axis).astype(jnp.int32)
        n_local = db["vectors"].shape[0]
        gids = jnp.where(out["ids"] >= 0, out["ids"] + shard * n_local, -1)
        d, i = _merge_topk(out["dists"], gids, cfg.k, model_axis)
        return jnp.where(jnp.isfinite(d), i, -1), d

    def _serve_graph(db, queries, programs, valid):
        return _graph_from_phat(db, queries, programs,
                                _estimate(db, programs), valid)

    serve_graph = jax.jit(jax.shard_map(
        _serve_graph, mesh=mesh,
        in_specs=(dspecs, qspec, pspec_each, vspec),
        out_specs=(qspec, qspec),
        check_vma=False))

    # same route with the selectivity estimate supplied by the caller (the
    # router already ran it to take the routing decision -- don't pay the
    # O(B x sample) evaluation twice per batch)
    serve_graph_phat = jax.jit(jax.shard_map(
        _graph_from_phat, mesh=mesh,
        in_specs=(dspecs, qspec, pspec_each, P(qspec[0]), vspec),
        out_specs=(qspec, qspec),
        check_vma=False))

    # -- brute route -----------------------------------------------------------
    def _serve_brute(db, queries, programs, valid):
        n_local = db["vectors"].shape[0]
        chunk = largest_divisor(n_local, prefbf_chunk)
        if cfg.use_pallas:
            # the scan chunk becomes the kernel's n-tile; keep it VMEM-sized
            # (the kernel pads the shard's row count internally)
            chunk = min(chunk, 512)
        ids, d = prefbf.prefbf_topk(
            db["vectors"], _scan_norms(db), db["attrs_int"],
            db["attrs_float"], queries, programs, k=cfg.k, chunk=chunk,
            use_pallas=cfg.use_pallas, valid=valid)
        shard = jax.lax.axis_index(model_axis).astype(jnp.int32)
        gids = jnp.where(ids >= 0, ids + shard * n_local, -1)
        d, i = _merge_topk(d, gids, cfg.k, model_axis)
        return jnp.where(jnp.isfinite(d), i, -1), d

    serve_brute = jax.jit(jax.shard_map(
        _serve_brute, mesh=mesh,
        in_specs=(dspecs, qspec, pspec_each, vspec),
        out_specs=(qspec, qspec),
        check_vma=False))

    fns = {"estimate": estimate, "serve_graph": serve_graph,
           "serve_graph_phat": serve_graph_phat, "serve_brute": serve_brute,
           "db_specs": dspecs, "query_spec": qspec}

    # -- compressed brute route (quant subsystem, sharded) --------------------
    if quant is not None:
        from ..quant import adc as quant_adc

        def _serve_brute_pq(db, queries, programs, valid):
            """Per shard: ADC LUT scan over the local uint8 codes -> exact
            float32 re-rank of the top rerank*k local candidates -> global
            ids -> cross-shard top-k merge.  The O(Ns) scan reads only codes;
            float32 rows are touched for the R re-rank candidates alone.
            With cfg.use_pallas the PQ scan runs the pq_adc kernel (the SQ
            fallback has no kernel and ignores the flag, like LocalBackend)."""
            n_local = db["norms"].shape[0]
            chunk = largest_divisor(n_local, prefbf_chunk)
            norms = _scan_norms(db)
            if quant == "pq":
                ids, d = quant_adc.pq_prefbf_topk(
                    db["codes"], norms, db["attrs_int"],
                    db["attrs_float"], queries, programs, db["centroids"],
                    db["vectors"], k=cfg.k, rerank=rerank, chunk=chunk,
                    use_pallas=cfg.use_pallas, valid=valid)
            else:
                ids, d = quant_adc.sq_prefbf_topk(
                    db["codes"], db["sq_lo"], db["sq_scale"], norms,
                    db["attrs_int"], db["attrs_float"], queries, programs,
                    db["vectors"], k=cfg.k, rerank=rerank, chunk=chunk,
                    valid=valid)
            shard = jax.lax.axis_index(model_axis).astype(jnp.int32)
            n_loc = jnp.asarray(n_local, jnp.int32)
            gids = jnp.where(ids >= 0, ids + shard * n_loc, -1)
            d, i = _merge_topk(d, gids, cfg.k, model_axis)
            return jnp.where(jnp.isfinite(d), i, -1), d

        fns["serve_brute_pq"] = jax.jit(jax.shard_map(
            _serve_brute_pq, mesh=mesh,
            in_specs=(dspecs, qspec, pspec_each, vspec),
            out_specs=(qspec, qspec),
            check_vma=False))

    return fns


def device_put_sharded_db(arrays: dict, mesh: Mesh, specs: dict) -> dict:
    return {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
            for k, v in arrays.items()}
