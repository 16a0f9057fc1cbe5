"""Selector routing shared by every execution backend (paper section 4.1).

The seed buried the route/partition logic inside ``FavorIndex.search``, so
the sharded serve path could never reuse it and the two paths drifted.  This
module owns the whole host-side online pipeline:

    compile filters -> estimate p_hat -> plan routes -> partition the batch
    -> backend.search_graph / backend.search_brute -> reassemble

``execute()`` is the single entry point; ``FavorIndex.query`` and
``ServeEngine`` both call it, with the backend (local single-host or sharded
multi-device) supplied as a parameter.  Identical queries therefore take
identical routes on every backend -- the selector decision is made exactly
once, here, from the backend's own selectivity estimate.
"""
from __future__ import annotations

import time
from contextlib import nullcontext
from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np

from . import batching
from . import filters as F
from . import selector
from .options import ROUTES, SearchOptions


@dataclass
class SearchResult:
    ids: np.ndarray      # (B, k) int64, -1 padded
    dists: np.ndarray    # (B, k) float32, +inf padded
    p_hat: np.ndarray    # (B,)
    routed_brute: np.ndarray  # (B,) bool
    # hops/path_td are per-query graph traversal diagnostics: 0 for
    # brute-routed (and cache-served) queries, and ``None`` for the whole
    # batch when a graph sub-batch ran on a backend that does not report
    # them (the sharded serve path returns only ids/dists from its top-k
    # merge) -- None-safe so operators can tell "no hops" from "unknown"
    hops: np.ndarray | None     # (B,) or None
    path_td: np.ndarray | None  # (B,) or None
    # waves: expansion rounds the traversal's lane-compacted while_loop ran
    # for the query's sub-batch (every lane in a stage shares the wave
    # count, so this is a batch-shape diagnostic, not a per-lane hop count)
    waves: np.ndarray | None = None  # (B,) or None
    # checked: the sampled estimate fell in the selector's check band and
    # the exact match count decided the route (selector.check_band)
    checked: np.ndarray | None = None  # (B,) bool or None
    elapsed_s: float = 0.0

    @property
    def qps(self) -> float:
        return len(self.ids) / max(self.elapsed_s, 1e-12)


@dataclass(eq=False)
class PendingExecution:
    """In-flight result of ``execute(..., defer=True)``.

    The host phase (filter compile, cache lookup, selectivity estimate,
    routing, bucket padding, backend dispatch) has already run and the
    backend's device work is in flight behind JAX's async dispatch;
    ``finish()`` blocks on the device transfers, reassembles the batch, and
    fires the backend's ``record_result`` hook plus the obs trace.  Passing
    ``hook_lock`` runs only those *mutating* host hooks (cache record, obs
    registry) under the lock -- the device sync itself never holds it, which
    is what lets a pipelined engine overlap one step's device wait with the
    next step's host phase.  ``finish()`` is idempotent: the first call
    materializes the SearchResult, later calls return the same object.
    """
    backend: object
    opts: SearchOptions
    b: int
    t0: float
    ids: np.ndarray
    dists: np.ndarray
    p_hat: np.ndarray
    routed_brute: np.ndarray
    hops: np.ndarray
    path_td: np.ndarray
    waves: np.ndarray
    miss: np.ndarray
    checked: np.ndarray | None = None
    tr: object = None
    obs: object = None
    programs: dict | None = None
    mq: object = None
    mprogs: dict | None = None
    mp_hat: np.ndarray | None = None
    plan: RoutePlan | None = None
    gi: np.ndarray | None = None
    bi: np.ndarray | None = None
    graph_out: dict | None = None
    brute_out: tuple | None = None
    graph_diag: bool = True
    waves_diag: bool = True
    _result: SearchResult | None = None

    def finish(self, hook_lock=None) -> SearchResult:
        if self._result is not None:
            return self._result
        ids, dists, miss = self.ids, self.dists, self.miss
        gi, bi = self.gi, self.bi
        with (self.tr.span("fetch") if self.tr is not None
              else nullcontext()):
            if self.graph_out is not None:
                out = self.graph_out
                ids[miss[gi]] = np.asarray(out["ids"])[:len(gi)]
                dists[miss[gi]] = np.asarray(out["dists"])[:len(gi)]
                if "hops" in out:
                    self.hops[miss[gi]] = np.asarray(out["hops"])[:len(gi)]
                    self.path_td[miss[gi]] = np.asarray(
                        out["path_td"])[:len(gi)]
                else:
                    self.graph_diag = False
                if "waves" in out:
                    self.waves[miss[gi]] = np.asarray(
                        out["waves"])[:len(gi)]
                else:
                    self.waves_diag = False
            if self.brute_out is not None:
                bid, bd = self.brute_out
                ids[miss[bi]] = np.asarray(bid)[:len(bi)]
                dists[miss[bi]] = np.asarray(bd)[:len(bi)]
        # the np.asarray conversions above synced the in-flight device work
        elapsed = time.perf_counter() - self.t0
        with (hook_lock if hook_lock is not None else nullcontext()):
            record = getattr(self.backend, "record_result", None)
            if record is not None and len(miss):
                with (self.tr.span("cache_record") if self.tr is not None
                      else nullcontext()):
                    record(np.asarray(self.mq), self.mprogs, self.opts,
                           ids[miss], dists[miss], self.mp_hat,
                           self.plan.brute)
            if self.tr is not None:
                self.tr.attrs["cache_hits"] = int(self.b - len(miss))
                self.tr.attrs["graph"] = int(
                    self.b - int(self.routed_brute.sum()))
                self.tr.attrs["brute"] = int(self.routed_brute.sum())
                programs = self.programs
                self.obs.finish_trace(
                    self.tr, p_hat=self.p_hat,
                    routed_brute=self.routed_brute, ef=self.opts.ef,
                    signatures=lambda: F.batch_signatures(programs))
        self._result = SearchResult(
            ids, dists, self.p_hat, self.routed_brute,
            self.hops if self.graph_diag else None,
            self.path_td if self.graph_diag else None,
            waves=self.waves if self.waves_diag else None,
            checked=self.checked, elapsed_s=elapsed)
        return self._result


@dataclass(frozen=True)
class RoutePlan:
    """Per-query routing decision: True -> PreFBF brute scan.  ``checked``
    marks the queries an exact match count routed."""
    p_hat: np.ndarray
    brute: np.ndarray
    checked: np.ndarray

    @property
    def graph_idx(self) -> np.ndarray:
        return np.nonzero(~self.brute)[0]

    @property
    def brute_idx(self) -> np.ndarray:
        return np.nonzero(self.brute)[0]


def broadcast_filters(filters, batch: int) -> list:
    """One filter -> one per query; otherwise the count must match."""
    if isinstance(filters, F.Filter):
        filters = [filters] * batch
    filters = list(filters)
    if len(filters) != batch:
        raise ValueError(f"expected one filter per query: got {len(filters)} "
                         f"filters for {batch} queries")
    return filters


def _no_span(name, **attrs):
    return nullcontext()


def compile_programs(filters, schema: F.Schema, batch: int,
                     width: int = 8, span=_no_span) -> dict:
    """One DNF program per query, stacked and on the device: ``lower``
    compiles the batch on the host (``filters.compile_batch``), ``upload``
    copies its arrays to the device."""
    with span("lower"):
        progs = F.compile_batch(broadcast_filters(filters, batch), schema,
                                width)
    with span("upload"):
        return {k: jnp.asarray(v) for k, v in progs.items()}


def plan_routes(p_hat: np.ndarray, lam: float,
                force: str | None = None,
                p_exact: np.ndarray | None = None) -> RoutePlan:
    """Route each query by estimated selectivity (p_hat < lambda -> brute),
    or by its exact selectivity where ``p_exact`` holds one (NaN elsewhere;
    see ``selector.route``); ``force`` pins every query to one route
    (validated, not pattern-matched: a typo'd route name raises instead of
    silently auto-routing)."""
    if force not in ROUTES:
        raise ValueError(f"force must be one of {ROUTES}, got {force!r}")
    # a NaN estimate (empty selectivity sample: freshly-created live index
    # with no merged base) routes as p_hat=1 -- graph side, where the
    # delta-compose path serves it
    p_hat = np.nan_to_num(np.asarray(p_hat, np.float32), nan=1.0)
    checked = np.zeros(p_hat.shape, bool)
    if force == "brute":
        brute = np.ones(p_hat.shape, bool)
    elif force == "graph":
        brute = np.zeros(p_hat.shape, bool)
    else:
        brute = selector.route(p_hat, lam, p_exact)
        if p_exact is not None:
            checked = ~np.isnan(np.asarray(p_exact))
    return RoutePlan(p_hat, brute, checked)


def check_estimates(backend, programs: dict, valid, p_hat: np.ndarray, *,
                    span, registry=None) -> np.ndarray | None:
    """Exact selectivity of the queries whose estimate falls in the
    selector's check band (``selector.check_band``), NaN for the rest; None
    when the band is empty or the backend counts no exact matches (it has
    no ``exact_selectivity``), so neither dispatches anything.

    ``programs`` / ``valid`` are the estimate's own (bucket-padded) batch:
    the count runs over all of it, at a shape warm-up compiled, and the band
    picks the rows it decides -- slicing the band out would compile a new
    program for every band size."""
    check = getattr(backend, "exact_selectivity", None)
    if check is None:
        return None
    n, total = backend.sample_size
    band = selector.check_band(p_hat, n, total, backend.sel_cfg.lam)
    if not band.any():
        return None
    with span("check", rows=int(band.sum())):
        # the tenant sidecar is host-side cache state, never a device input
        progs = {k: v for k, v in programs.items() if k != "scope"}
        batching.record(registry, "check", int(progs["valid"].shape[0]),
                        int(band.sum()))
        exact = np.asarray(check(progs, valid=valid))[:len(p_hat)]
    return np.where(band, exact, np.nan).astype(np.float32)


def take_programs(programs: dict, idx: np.ndarray) -> dict:
    """Row-slice a stacked program dict to a sub-batch (device-side gather;
    the seed's ``np.asarray(v)[idx]`` forced a device->host->device round
    trip per route split)."""
    idx = jnp.asarray(np.asarray(idx, np.int32))
    return {k: jnp.take(jnp.asarray(v), idx, axis=0)
            for k, v in programs.items()}


def execute(backend, queries, filters, opts: SearchOptions, *,
            registry=None, scopes=None, obs=None,
            defer: bool = False) -> SearchResult | PendingExecution:
    """Run one filtered-ANNS batch through ``backend`` (paper Fig. 1 online
    phase): result-cache fast path -> estimate -> route -> per-route
    execution -> reassembly.

    When ``opts.batch`` is a BatchSpec, the estimate call and each route
    sub-batch are bucket-padded before hitting the backend: pad rows carry
    an always-false filter program plus a False entry in the ``valid`` mask
    the backend receives, and are stripped on reassembly -- so the compiled
    shape set is bounded by the bucket ladder while results stay
    bit-identical to the unpadded path.  ``registry`` (a
    batching.ShapeRegistry) optionally records every compiled-entry-point
    shape and the pad overhead paid.

    Backends may optionally implement two duck-typed hooks (the cache
    subsystem's ``CachingBackend`` does; plain backends need neither):

      lookup_result(queries, programs, opts) -> None | {"hit": (B,) bool,
          "ids"/"dists"/"p_hat"/"routed_brute": hit-row arrays}
          served *before* estimation, so a hit skips the whole pipeline.
      record_result(queries, programs, opts, ids, dists, p_hat, routed_brute)
          called with the freshly computed miss rows after execution.

    ``scopes`` is an optional (B,) int array of per-request tenant/session
    scope ids (0 = unscoped).  It rides the stacked program dict as a
    ``"scope"`` sidecar row -- so it is sliced, padded (with 0) and
    sub-batched in lockstep with the filter programs -- but only when the
    backend declares ``scope_aware`` (the cache subsystem's CachingBackend,
    which keys its semantic/candidate layers on it and strips it before any
    inner compiled call); plain device backends never see it, keeping their
    jit pytree signatures unchanged.

    ``obs`` is an optional ``repro.obs.Obs``: when its tracer samples this
    batch, every pipeline stage below runs inside a span (wall time, route,
    bucket shape, pad fraction, cache hits), each of which -- when the spec
    enables annotations -- is also a ``jax.profiler.TraceAnnotation`` on
    the profiler's clock.  The compile splits into ``lower`` (the batch's
    filters to program arrays on the host) and ``upload``; the estimate
    into ``dispatch`` (pad, enqueue), ``wait`` (the host sync on its
    result) and, when the selector's check band holds rows, ``check`` (the
    exact count's dispatch and sync); ``finish`` adds ``fetch``, the
    device-to-host copies of the route outputs.
    Obs hooks only *observe*; results are bit-identical with obs absent,
    disabled, or sampled out.

    ``defer=True`` returns a ``PendingExecution`` after the host phase:
    the backend searches are *dispatched* (device work queued behind JAX
    async dispatch) but not fetched, and no mutating hook has fired.  The
    caller finishes the step -- possibly from another thread, possibly
    after dispatching more steps -- with ``pending.finish()``, which
    yields the identical SearchResult the synchronous path returns.
    """
    backend.validate(opts)
    queries = jnp.asarray(np.ascontiguousarray(queries, np.float32))
    b = queries.shape[0]

    tr = obs.start_trace(b) if obs is not None else None
    _span = _no_span if tr is None else tr.span

    with _span("compile", rows=b):
        programs = compile_programs(filters, backend.schema, b, span=_span)
    if scopes is not None and getattr(backend, "scope_aware", False):
        scopes = np.asarray(scopes, np.int32)
        if scopes.shape != (b,):
            raise ValueError(f"scopes must be shaped ({b},), "
                             f"got {scopes.shape}")
        if scopes.any():   # all-zero means unscoped: skip the sidecar
            programs["scope"] = jnp.asarray(scopes)
    spec = opts.batch

    t0 = time.perf_counter()
    ids = np.full((b, opts.k), -1, np.int64)
    dists = np.full((b, opts.k), np.inf, np.float32)
    p_hat = np.zeros((b,), np.float32)
    routed_brute = np.zeros((b,), bool)
    hops = np.zeros((b,), np.int64)
    path_td = np.zeros((b,), np.int64)
    waves = np.zeros((b,), np.int64)
    lookup = getattr(backend, "lookup_result", None)
    with _span("cache_lookup") as sp:
        cached = (lookup(np.asarray(queries), programs, opts)
                  if lookup else None)
        if sp is not None:
            sp.attrs["hits"] = (int(np.asarray(cached["hit"]).sum())
                                if cached is not None else 0)
    if cached is not None:
        hi = np.nonzero(np.asarray(cached["hit"], bool))[0]
        ids[hi] = np.asarray(cached["ids"])
        dists[hi] = np.asarray(cached["dists"])
        p_hat[hi] = np.asarray(cached["p_hat"])
        routed_brute[hi] = np.asarray(cached["routed_brute"])
        miss = np.nonzero(~np.asarray(cached["hit"], bool))[0]
    else:
        miss = np.arange(b)

    pend = PendingExecution(
        backend=backend, opts=opts, b=b, t0=t0, ids=ids, dists=dists,
        p_hat=p_hat, routed_brute=routed_brute, hops=hops, path_td=path_td,
        waves=waves, miss=miss, tr=tr, obs=obs, programs=programs)

    if len(miss):
        # avoid re-slicing (device round-trips) when a sub-batch is the
        # whole batch -- the common case for plain (hook-less) backends
        full = len(miss) == b
        mq = queries if full else queries[miss]
        mprogs = programs if full else take_programs(programs, miss)
        with _span("estimate", rows=len(miss)) as sp:
            with _span("dispatch"):
                if spec is None:
                    batching.record(registry, "estimate", len(miss),
                                    len(miss))
                    eprogs, evalid = mprogs, None
                    est = backend.estimate(mprogs)
                else:
                    eprogs, evalid = batching.pad_programs(spec, mprogs)
                    batching.record(registry, "estimate", len(evalid),
                                    len(miss))
                    if sp is not None:
                        sp.attrs["bucket"] = int(len(evalid))
                    est = backend.estimate(eprogs, valid=evalid)
            with _span("wait"):
                mp_hat = np.asarray(est)[:len(miss)]
            p_exact = (check_estimates(backend, eprogs, evalid, mp_hat,
                                       registry=registry, span=_span)
                       if opts.force is None else None)
        with _span("route") as sp:
            plan = plan_routes(mp_hat, backend.sel_cfg.lam, opts.force,
                               p_exact)
            if sp is not None:
                sp.attrs["graph"] = int(len(plan.graph_idx))
                sp.attrs["brute"] = int(len(plan.brute_idx))
                sp.attrs["checked"] = int(plan.checked.sum())
        p_hat[miss] = plan.p_hat
        routed_brute[miss] = plan.brute
        checked = np.zeros((b,), bool)
        checked[miss] = plan.checked
        pend.checked = checked

        gi, bi = plan.graph_idx, plan.brute_idx
        pend.mq, pend.mprogs, pend.mp_hat = mq, mprogs, mp_hat
        pend.plan, pend.gi, pend.bi = plan, gi, bi
        if len(gi):
            with _span("graph", rows=len(gi)) as gspan:
                whole = len(gi) == len(miss)
                gq = mq if whole else mq[gi]
                gprogs = mprogs if whole else take_programs(mprogs, gi)
                gp = mp_hat if whole else mp_hat[gi]
                gvalid = None
                if spec is not None:
                    with _span("pad"):
                        gq, gprogs, gp, gvalid = batching.pad_to_bucket(
                            spec, gq, gprogs, gp)
                bucket = int(gq.shape[0])
                if gspan is not None:
                    gspan.attrs["bucket"] = bucket
                    gspan.attrs["pad_frac"] = 1.0 - len(gi) / bucket
                batching.record(registry, "graph", bucket, len(gi), opts)
                with _span("search"):
                    pend.graph_out = backend.search_graph(
                        gq, gprogs, jnp.asarray(gp), opts, valid=gvalid)
        if len(bi):
            with _span("brute", rows=len(bi)) as bspan:
                whole = len(bi) == len(miss)
                bq = mq if whole else mq[bi]
                bprogs = mprogs if whole else take_programs(mprogs, bi)
                bvalid = None
                if spec is not None:
                    with _span("pad"):
                        bq, bprogs, _, bvalid = batching.pad_to_bucket(
                            spec, bq, bprogs)
                bucket = int(bq.shape[0])
                if bspan is not None:
                    bspan.attrs["bucket"] = bucket
                    bspan.attrs["pad_frac"] = 1.0 - len(bi) / bucket
                batching.record(registry, "brute", bucket, len(bi), opts)
                with _span("search"):
                    pend.brute_out = backend.search_brute(bq, bprogs, opts,
                                                          valid=bvalid)

    return pend if defer else pend.finish()
