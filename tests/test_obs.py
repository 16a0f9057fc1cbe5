"""Unified observability layer (repro.obs).

Acceptance bars from the PR-8 issue:
  * every stats surface served from ONE metrics registry, exported both as
    a JSON snapshot and prometheus text exposition (golden-tested)
  * route traces cover every ``router.execute`` stage, nest correctly under
    coalesced front-end batches, and feed the slow-query ring
  * estimator-accuracy probes measure |p_hat - p_true| against the real
    corpus; route-confusion shadows populate (chosen, faster) counters
  * ``ObsSpec(enabled=False)`` (and obs=None) is bit-identical to enabled
  * ``reset_stats()`` cascades through the registry: engine counters,
    frontend tenant/coalesce ledgers, cache layer counters, trace rings
plus the satellite contracts: injectable monotonic clock (deterministic
histograms/spans under a fake ``time_fn``) and histogram ``le`` edges.

No pytest-asyncio: async scenarios run through ``asyncio.run``.
"""
import asyncio
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cache import CachingBackend
from repro.core import (BatchSpec, CacheSpec, FrontEndSpec, LocalBackend,
                        ObsSpec, SearchOptions, router)
from repro.core import filters as F
from repro.core.search import favor_graph_search
from repro.obs import MetricsRegistry, Obs, RequestTrace
from repro.obs.probes import innermost, true_fraction
from repro.obs.trace import sample_period
from repro.serving import FrontEnd, ServeEngine

OPTS = SearchOptions(k=5, ef=48, batch=BatchSpec(min_bucket=4, max_bucket=16))


def _queries(n, d, seed=0):
    return np.random.default_rng(seed).normal(size=(n, d)).astype(np.float32)


def _flt(schema):
    return F.paper_filters(schema)["equality_bool"]


class FakeClock:
    """Monotonic fake: every call advances by ``tick`` seconds."""

    def __init__(self, tick=0.001):
        self.t = 0.0
        self.tick = tick

    def __call__(self):
        self.t += self.tick
        return self.t


# ---------------------------------------------------------------------------
# Registry primitives
# ---------------------------------------------------------------------------
def test_counter_labels_and_monotonicity():
    reg = MetricsRegistry()
    c = reg.counter("favor_x_total", "x", labels=("route",))
    c.inc(route="graph")
    c.inc(2.5, route="graph")
    c.inc(route="brute")
    assert c.value(route="graph") == 3.5
    assert c.value(route="brute") == 1.0
    assert c.value(route="never") == 0.0
    assert c.total() == 4.5
    with pytest.raises(ValueError, match="cannot decrease"):
        c.inc(-1.0, route="graph")
    with pytest.raises(ValueError, match="labels"):
        c.inc(tenant="a")  # wrong label name
    with pytest.raises(ValueError, match="labels"):
        c.inc()            # missing label


def test_registry_registration_idempotent_and_conflicting():
    reg = MetricsRegistry()
    a = reg.counter("favor_y_total", "y")
    assert reg.counter("favor_y_total") is a
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("favor_y_total")
    with pytest.raises(ValueError, match="already registered"):
        reg.counter("favor_y_total", labels=("route",))
    with pytest.raises(ValueError, match="bad metric name"):
        reg.counter("favor-y", "dashes are not prometheus names")


def test_histogram_bucket_edges_are_inclusive_upper_bounds():
    reg = MetricsRegistry()
    h = reg.histogram("favor_h", "h", buckets=(0.1, 1.0))
    # prometheus ``le`` semantics: a sample equal to the bound lands IN it
    for v in (0.05, 0.1, 0.5, 1.0, 2.0):
        h.observe(v)
    snap = reg.snapshot()["histograms"]["favor_h"]["series"][""]
    assert snap["buckets"] == [["0.1", 2], ["1", 4], ["+Inf", 5]]
    assert snap["count"] == 5
    assert snap["sum"] == pytest.approx(3.65)
    # observe_many bins identically (numpy searchsorted path)
    h2 = reg.histogram("favor_h2", "h", buckets=(0.1, 1.0))
    h2.observe_many([0.05, 0.1, 0.5, 1.0, 2.0])
    assert (reg.snapshot()["histograms"]["favor_h2"]["series"][""]
            == snap)
    with pytest.raises(ValueError, match="strictly"):
        reg.histogram("favor_h3", "h", buckets=(1.0, 1.0))


def test_histogram_percentile_interpolation():
    reg = MetricsRegistry()
    h = reg.histogram("favor_p", "p", buckets=(1.0, 2.0, 4.0))
    assert h.percentile(50) is None
    h.observe_many([0.5] * 50 + [1.5] * 50)
    assert h.percentile(25) == pytest.approx(0.5)
    assert h.percentile(100) == pytest.approx(2.0)
    assert 1.0 < h.percentile(75) <= 2.0


def test_prometheus_exposition_golden():
    reg = MetricsRegistry()
    c = reg.counter("favor_requests_total", "Requests served, by route",
                    labels=("route",))
    c.inc(3, route="graph")
    c.inc(route="brute")
    reg.gauge("favor_delta_rows", "Live delta rows").set(12)
    h = reg.histogram("favor_latency_seconds", "Latency", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 2.0):
        h.observe(v)
    reg.register_view("cache", lambda: {"semantic": {"hits": 2, "misses": 1,
                                                     "note": "non-numeric"}})
    assert reg.prometheus_text() == """\
# HELP favor_requests_total Requests served, by route
# TYPE favor_requests_total counter
favor_requests_total{route="brute"} 1
favor_requests_total{route="graph"} 3
# HELP favor_delta_rows Live delta rows
# TYPE favor_delta_rows gauge
favor_delta_rows 12
# HELP favor_latency_seconds Latency
# TYPE favor_latency_seconds histogram
favor_latency_seconds_bucket{le="0.1"} 1
favor_latency_seconds_bucket{le="1"} 2
favor_latency_seconds_bucket{le="+Inf"} 3
favor_latency_seconds_sum 2.55
favor_latency_seconds_count 3
# HELP favor_view Flattened numeric leaves of registered stats views
# TYPE favor_view gauge
favor_view{view="cache",path="semantic.hits"} 2
favor_view{view="cache",path="semantic.misses"} 1
"""


def test_snapshot_is_json_able_and_reset_zeroes():
    reg = MetricsRegistry()
    reg.counter("favor_a_total", "a").inc(7)
    reg.histogram("favor_b", "b", buckets=(1.0,)).observe(0.5)
    reg.register_view("v", lambda: {"x": 1})
    snap = json.loads(json.dumps(reg.snapshot()))
    assert snap["counters"]["favor_a_total"]["series"][""] == 7
    assert snap["views"]["v"] == {"x": 1}
    hooked = []
    reg.on_reset(lambda: hooked.append(True))
    reg.reset()
    assert hooked == [True]
    snap = reg.snapshot()
    assert snap["counters"]["favor_a_total"]["series"][""] == 0
    assert snap["histograms"]["favor_b"]["series"][""]["count"] == 0


def test_sample_period():
    assert sample_period(0.0) == 0
    assert sample_period(1.0) == 1
    assert sample_period(0.5) == 2
    assert sample_period(0.1) == 10
    assert sample_period(1e-9) >= 1


# ---------------------------------------------------------------------------
# Spans + fake clock determinism
# ---------------------------------------------------------------------------
def test_span_nesting_and_fake_clock_determinism():
    clock = FakeClock(tick=1.0)
    tr = RequestTrace(1, batch=4, time_fn=clock)   # t0 = 1
    with tr.span("outer", rows=4):                 # t0 = 2
        with tr.span("inner"):                     # t0 = 3, t1 = 4
            pass
    # outer t1 = 5
    tr.finish()                                    # t1 = 6
    assert [s.name for s in tr.spans] == ["outer"]
    outer = tr.spans[0]
    assert [c.name for c in outer.children] == ["inner"]
    assert outer.attrs == {"rows": 4}
    assert outer.duration_s == pytest.approx(3.0)
    assert outer.children[0].duration_s == pytest.approx(1.0)
    assert tr.duration_s == pytest.approx(5.0)
    assert tr.stage_ms() == {"outer": pytest.approx(3000.0)}
    d = tr.to_dict()
    assert d["spans"][0]["children"][0]["name"] == "inner"


def test_obsspec_validation():
    ObsSpec()  # defaults valid
    with pytest.raises(ValueError, match="trace_sample"):
        ObsSpec(trace_sample=1.5)
    with pytest.raises(ValueError, match="probe_sample"):
        ObsSpec(probe_sample=-0.1)
    with pytest.raises(ValueError, match="trace_cap"):
        ObsSpec(trace_cap=0)
    with pytest.raises(ValueError, match="slow_ms"):
        ObsSpec(slow_ms=-1.0)
    with pytest.raises(ValueError, match="latency_buckets"):
        ObsSpec(latency_buckets=(0.1, 0.1))
    assert ObsSpec(slow_ms=None).slow_ms is None
    assert ObsSpec().with_(probe_sample=0.5).probe_sample == 0.5
    with pytest.raises(TypeError):
        Obs("not a spec")


# ---------------------------------------------------------------------------
# Engine integration: one registry serves every stats surface
# ---------------------------------------------------------------------------
def _drive(eng, schema, n=12, seed=0, d=16):
    qs = _queries(n, d, seed)
    flt = _flt(schema)
    for i in range(n):
        eng.submit(qs[i], flt)
    out = eng.drain()
    assert len(out) == n
    return out


def test_engine_stats_served_from_registry(small_index, small_dataset):
    _, _, schema = small_dataset
    eng = ServeEngine(LocalBackend(small_index), OPTS, max_batch=8)
    _drive(eng, schema)
    st = eng.stats
    assert st["graph"] + st["brute"] == 12
    assert st["batches"] == 2
    assert st["obs"]["traces"] == 2           # trace_sample defaults to 1.0
    # the same numbers through both machine exports
    snap = eng.obs.snapshot()
    served = snap["counters"]["favor_requests_total"]["series"]
    assert sum(served.values()) == 12
    assert snap["histograms"]["favor_request_latency_seconds"][
        "series"][""]["count"] == 12
    assert snap["histograms"]["favor_p_hat"]["series"][""]["count"] == 12
    assert snap["views"]["batching"]["pad_rows"] >= 0
    text = eng.obs.prometheus_text()
    assert "# TYPE favor_requests_total counter" in text
    assert "favor_batches_total 2" in text
    assert 'favor_view{view="scorers",' in text


def test_trace_spans_cover_every_router_stage(small_index, small_dataset):
    _, _, schema = small_dataset
    # cache-capable backend: the lookup/record stages are real, not skipped
    cb = CachingBackend(LocalBackend(small_index), CacheSpec())
    eng = ServeEngine(cb, OPTS, max_batch=8)
    _drive(eng, schema, n=8)
    tr = eng.obs.tracer.traces[-1]
    names = [s.name for s in tr.spans]
    for stage in ("compile", "cache_lookup", "estimate", "route",
                  "cache_record"):
        assert stage in names, names
    assert ("graph" in names) or ("brute" in names)
    # route sub-batch spans nest their pad + search steps
    route_sp = next(s for s in tr.spans if s.name in ("graph", "brute"))
    kids = [c.name for c in route_sp.children]
    assert kids == ["pad", "search"], kids
    assert route_sp.attrs["rows"] >= 1
    assert route_sp.attrs["bucket"] in OPTS.batch.buckets()
    assert 0.0 <= route_sp.attrs["pad_frac"] <= 1.0
    # every top-level stage fed the shared stage histogram
    hist = eng.obs.registry.snapshot()["histograms"]["favor_stage_seconds"]
    stages = {k for k in hist["series"]}
    assert 'stage="estimate"' in stages and 'stage="route"' in stages


def test_slow_query_log_and_sampling(small_index, small_dataset):
    _, _, schema = small_dataset
    # slow_ms=0: every traced batch is "slow"; trace_sample=0.5 -> 1-in-2
    eng = ServeEngine(LocalBackend(small_index), OPTS, max_batch=4,
                      obs=ObsSpec(trace_sample=0.5, slow_ms=0.0))
    _drive(eng, schema, n=16)   # 4 batches -> batches 1 and 3 traced
    assert eng.stats["batches"] == 4
    assert eng.stats["obs"]["traces"] == 2
    slow = list(eng.obs.tracer.slow_log)
    assert len(slow) == 8       # per-request entries for the traced batches
    sq = slow[0]
    assert sq.route in ("graph", "brute")
    assert sq.ef == OPTS.ef
    assert 0.0 <= sq.p_hat <= 1.0
    assert sq.signature            # canonical filter signature, non-empty
    assert set(sq.stages_ms) >= {"compile", "estimate", "route"}
    assert sq.total_ms >= 0.0
    d = sq.to_dict()
    assert d["signature"] == sq.signature
    # slow_ms=None disables the ring entirely
    eng2 = ServeEngine(LocalBackend(small_index), OPTS, max_batch=4,
                       obs=ObsSpec(slow_ms=None))
    _drive(eng2, schema, n=8)
    assert len(eng2.obs.tracer.slow_log) == 0


def test_obs_disabled_is_bit_identical_and_inert(small_index, small_dataset):
    _, _, schema = small_dataset
    qs = _queries(10, 16, seed=5)
    flt = _flt(schema)
    backend = LocalBackend(small_index)
    # router level: obs wired vs. not
    obs = Obs(ObsSpec(trace_sample=1.0))
    r_obs = router.execute(backend, qs, flt, OPTS, obs=obs)
    r_off = router.execute(backend, qs, flt, OPTS, obs=None)
    assert np.array_equal(r_obs.ids, r_off.ids)
    assert np.array_equal(r_obs.dists, r_off.dists)
    # engine level: ObsSpec(enabled=False) builds no tracer/probes and
    # serves identical responses
    eng_on = ServeEngine(LocalBackend(small_index), OPTS, max_batch=8)
    eng_off = ServeEngine(LocalBackend(small_index), OPTS, max_batch=8,
                          obs=ObsSpec(enabled=False))
    assert eng_off.obs.tracer is None and not eng_off.obs.wants_probe
    out_on = _drive(eng_on, schema, n=12, seed=9)
    out_off = _drive(eng_off, schema, n=12, seed=9)
    for a, b in zip(out_on, out_off):
        assert np.array_equal(a.ids, b.ids)
        assert np.array_equal(a.dists, b.dists)
        assert a.route == b.route
    assert eng_off.stats["obs"] == {"enabled": False, "trace_sample": 1.0}
    # counters still work with obs disabled (registry stays live)
    assert eng_off.stats["graph"] + eng_off.stats["brute"] == 12


def test_time_fn_injection_is_deterministic(small_index, small_dataset):
    _, _, schema = small_dataset

    def latencies(seed):
        eng = ServeEngine(LocalBackend(small_index), OPTS, max_batch=4,
                          time_fn=FakeClock(tick=0.001),
                          obs=ObsSpec(slow_ms=None))
        _drive(eng, schema, n=8, seed=seed)
        stages = {s.name: s.duration_s for s in eng.obs.tracer.traces[0].spans}
        return list(eng.latencies), stages

    lat1, st1 = latencies(3)
    lat2, st2 = latencies(3)
    # two runs under the fake clock agree exactly, tick for tick
    assert lat1 == lat2
    assert st1 == st2
    assert all(lat > 0 for lat in lat1)


# ---------------------------------------------------------------------------
# Probes: estimator accuracy + route confusion
# ---------------------------------------------------------------------------
def test_true_fraction_matches_direct_mask(small_index, small_dataset):
    _, attrs, schema = small_dataset
    backend = LocalBackend(small_index)
    assert innermost(backend) is backend
    flt = _flt(schema)
    prog = F.compile_filter(flt, schema)
    want = float(np.asarray(
        F.eval_program(prog, attrs.ints, attrs.floats)).mean())
    assert true_fraction(backend, flt) == pytest.approx(want)
    assert true_fraction(backend, F.TrueFilter()) == pytest.approx(1.0)


def test_estimator_probe_against_known_distribution(small_index,
                                                    small_dataset):
    _, _, schema = small_dataset
    backend = LocalBackend(small_index)
    eng = ServeEngine(backend, OPTS, max_batch=8,
                      obs=ObsSpec(probe_sample=1.0, slow_ms=None))
    out = _drive(eng, schema, n=16)      # 2 batches -> 2 probes
    snap = eng.obs.snapshot()
    probes = snap["counters"]["favor_estimator_probes_total"]["series"]
    assert sum(probes.values()) == 2
    err = snap["histograms"]["favor_estimator_abs_error"]["series"][""]
    assert err["count"] == 2
    # single filter everywhere: each probe's error is |p_hat - p_true|
    p_true = true_fraction(backend, _flt(schema))
    p_hat = out[0].p_hat
    assert err["sum"] == pytest.approx(2 * abs(p_hat - p_true))
    # equality_bool sits far above lambda on both estimate and truth, and
    # the graph route itself implies p_hat >= lambda: no route flips
    lam = float(backend.sel_cfg.lam)
    assert p_true >= lam and p_hat >= lam
    flips = snap["counters"]["favor_estimator_route_flips_total"]["series"]
    assert sum(flips.values()) == 0


def test_route_confusion_shadow_populates(small_index, small_dataset):
    _, _, schema = small_dataset
    eng = ServeEngine(LocalBackend(small_index), OPTS, max_batch=8,
                      obs=ObsSpec(shadow_sample=1.0, slow_ms=None))
    out = _drive(eng, schema, n=16)
    shadow = eng.obs.snapshot()["counters"]["favor_route_shadow_total"]
    assert sum(shadow["series"].values()) == 2    # 1 shadow per batch
    chosen_routes = {r.route for r in out}
    for key in shadow["series"]:
        assert any(f'chosen="{r}"' in key for r in chosen_routes), key


# ---------------------------------------------------------------------------
# Front-end: coalesced-batch traces + the full reset cascade
# ---------------------------------------------------------------------------
def test_frontend_coalesced_batch_traces(small_index, small_dataset):
    _, _, schema = small_dataset

    async def main():
        cb = CachingBackend(LocalBackend(small_index), CacheSpec())
        eng = ServeEngine(cb, OPTS, max_batch=16)
        fe = FrontEnd(eng, FrontEndSpec(coalesce_ms=25.0, coalesce_target=8))
        qs = _queries(8, 16, seed=21)
        outs = await asyncio.gather(
            *[fe.submit(qs[i], _flt(schema)) for i in range(8)])
        st = fe.stats
        traces = list(eng.obs.tracer.traces)
        await fe.close()
        return outs, st, traces

    outs, st, traces = asyncio.run(main())
    assert len(outs) == 8
    # the hold window coalesced concurrent submits into fewer dispatches;
    # each dispatched batch carries one span tree covering the pipeline
    assert st["coalesce"]["dispatches"] == len(traces) > 0
    total = 0
    for tr in traces:
        names = [s.name for s in tr.spans]
        # the slow-query log follows the pipeline only in a slow batch
        if names[-1] == "slow_log":
            names.pop()
        assert names[0] == "compile" and names[-1] == "cache_record", names
        for sp in tr.spans:     # spans nest: children close inside parents
            for c in sp.children:
                assert sp.t0 <= c.t0 and c.t1 <= sp.t1
        total += tr.batch
    assert total == 8


def test_reset_cascade_zeroes_every_surface(small_index, small_dataset):
    _, _, schema = small_dataset

    async def main():
        cb = CachingBackend(LocalBackend(small_index), CacheSpec())
        eng = ServeEngine(cb, OPTS, max_batch=8)
        fe = FrontEnd(eng, FrontEndSpec(coalesce_ms=5.0, coalesce_target=8))
        qs = _queries(8, 16, seed=23)
        flt = _flt(schema)

        async def burst():
            return await asyncio.gather(
                *[fe.submit(qs[i], flt) for i in range(8)])

        await burst()
        await burst()            # repeat traffic: populates cache hits
        before = fe.stats
        fe.reset_stats()         # one call cascades through the registry
        after = fe.stats
        await burst()            # cached ENTRIES survived the counter reset
        served_after = fe.stats
        await fe.close()
        return before, after, served_after

    before, after, warm = asyncio.run(main())
    # ...counters were non-zero before the reset
    assert before["tenants"]["default"]["served"] == 16
    assert before["coalesce"]["dispatches"] > 0
    eng_b = before["engine"]
    assert eng_b["graph"] + eng_b["brute"] == 16 and eng_b["batches"] > 0
    assert eng_b["cache"]["semantic"]["hits"] > 0
    assert eng_b["obs"]["traces"] > 0
    # ...and all zero after
    assert after["tenants"]["default"]["served"] == 0
    assert "p99_ms" not in after["tenants"]["default"]  # window cleared
    assert after["coalesce"]["dispatches"] == 0
    eng_a = after["engine"]
    assert eng_a["graph"] == eng_a["brute"] == eng_a["batches"] == 0
    assert eng_a["obs"]["traces"] == 0
    for layer in ("selectivity", "candidates", "semantic"):
        st = eng_a["cache"][layer]
        assert st["hits"] == st["misses"] == 0
    assert eng_a["batching"]["pad_rows"] == 0
    # entries survived: the post-reset burst is served from the warm cache
    assert warm["engine"]["cache"]["semantic"]["hits"] > 0
    assert warm["tenants"]["default"]["served"] == 8


def test_frontend_ledgers_in_exposition(small_index, small_dataset):
    _, _, schema = small_dataset

    async def main():
        cb = CachingBackend(LocalBackend(small_index), CacheSpec())
        eng = ServeEngine(cb, OPTS, max_batch=8)
        fe = FrontEnd(eng, FrontEndSpec(coalesce_ms=2.0))
        qs = _queries(4, 16, seed=27)
        await asyncio.gather(
            *[fe.submit(qs[i], _flt(schema)) for i in range(4)])
        text = eng.obs.prometheus_text()
        snap = eng.obs.snapshot()
        await fe.close()
        return text, snap

    text, snap = asyncio.run(main())
    assert ('favor_view{view="frontend",path="tenants.default.served"} 4'
            in text)
    assert 'favor_view{view="cache",path="semantic.' in text
    assert snap["views"]["frontend"]["tenants"]["default"]["served"] == 4


# ---------------------------------------------------------------------------
# One timeline: span paths, annotations, lock waits, queue time, compiles
# ---------------------------------------------------------------------------
def _stage_series(eng) -> dict:
    hist = eng.obs.registry.snapshot()["histograms"]["favor_stage_seconds"]
    return {k[len('stage="'):-1]: v for k, v in hist["series"].items()}


def test_child_spans_recorded_under_their_paths(small_index, small_dataset):
    _, _, schema = small_dataset
    eng = ServeEngine(LocalBackend(small_index), OPTS, max_batch=8,
                      time_fn=FakeClock(tick=0.001),
                      obs=ObsSpec(slow_ms=None))
    _drive(eng, schema, n=16)
    series = _stage_series(eng)
    traces = list(eng.obs.tracer.traces)
    assert len(traces) == 2
    route = "graph" if "graph" in series else "brute"
    for path in ("estimate/dispatch", "estimate/wait", f"{route}/pad",
                 f"{route}/search", "fetch"):
        assert series[path]["count"] == 2, path
    # children never land under bare names: host_ms_per_batch subtracts a
    # bare "search" stage, which must stay unrecorded
    assert "search" not in series and "pad" not in series
    assert "dispatch" not in series and "wait" not in series
    # top-level stages sum exactly what the traces' top-level spans took
    for name in ("compile", "cache_lookup", "estimate", "route", route,
                 "fetch"):
        want = sum(sp.duration_s for tr in traces for sp in tr.spans
                   if sp.name == name)
        assert series[name]["sum"] == pytest.approx(want), name
    # every span of every trace fed the histogram exactly once
    n_spans = sum(len(list(tr.walk())) for tr in traces)
    assert sum(v["count"] for v in series.values()) == n_spans


def test_compile_span_splits_into_lower_and_upload(small_index,
                                                  small_dataset):
    """The compile stage opens ``lower`` (the batched filter compile on
    the host) then ``upload`` (the programs' copy to the device), and keeps
    its own attributes."""
    _, _, schema = small_dataset
    qs = _queries(6, 16, seed=3)
    obs = Obs(ObsSpec(trace_sample=1.0, slow_ms=None),
              time_fn=FakeClock(tick=0.001))
    router.execute(LocalBackend(small_index), qs, _flt(schema), OPTS,
                   obs=obs)
    tr = obs.tracer.traces[-1]
    comp = next(sp for sp in tr.spans if sp.name == "compile")
    assert comp.attrs == {"rows": 6}
    assert [c.name for c in comp.children] == ["lower", "upload"]
    assert [c.path for c in comp.children] == ["compile/lower",
                                               "compile/upload"]
    for c in comp.children:
        assert c.attrs == {} and not c.children
        assert comp.t0 <= c.t0 and c.t1 <= comp.t1
    hist = obs.registry.snapshot()["histograms"]["favor_stage_seconds"]
    for path in ("compile", "compile/lower", "compile/upload"):
        assert hist["series"][f'stage="{path}"']["count"] == 1, path


def test_estimate_wait_fetch_and_slow_log_spans(small_index, small_dataset):
    _, _, schema = small_dataset
    eng = ServeEngine(LocalBackend(small_index), OPTS, max_batch=8,
                      obs=ObsSpec(slow_ms=0.0))
    _drive(eng, schema, n=8)
    tr = eng.obs.tracer.traces[-1]
    paths = [sp.path for sp in tr.walk()]
    for path in ("estimate/dispatch", "estimate/wait", "fetch", "slow_log"):
        assert path in paths, paths
    assert [sp.name for sp in tr.spans][-1] == "slow_log"
    est = next(sp for sp in tr.spans if sp.name == "estimate")
    assert [c.name for c in est.children] == ["dispatch", "wait"]
    assert sum(c.duration_s for c in est.children) <= est.duration_s
    # the slow entries carry the pipeline's stages, not the log's own span
    sq = eng.obs.tracer.slow_log[0]
    assert "fetch" in sq.stages_ms and "slow_log" not in sq.stages_ms
    assert _stage_series(eng)["slow_log"]["count"] == 1


def test_lock_wait_and_queue_histograms_count_batches_and_requests(
        small_index, small_dataset):
    _, _, schema = small_dataset

    async def main():
        eng = ServeEngine(LocalBackend(small_index), OPTS, max_batch=4)
        fe = FrontEnd(eng, FrontEndSpec(coalesce_ms=5.0, coalesce_target=4,
                                        parallel_steps=2))
        qs = _queries(12, 16, seed=31)
        await asyncio.gather(*[fe.submit(qs[i], _flt(schema))
                               for i in range(12)])
        st = fe.stats
        snap = eng.obs.snapshot()
        await fe.close()
        return st, snap

    st, snap = asyncio.run(main())
    batches = st["engine"]["batches"]
    assert batches >= 3
    waits = snap["histograms"]["favor_engine_lock_wait_seconds"]["series"]
    assert waits['site="serve"']["count"] == st["coalesce"]["dispatches"]
    assert waits['site="finish"']["count"] == batches
    assert waits['site="hook"']["count"] == batches
    assert all(v["sum"] >= 0.0 for v in waits.values())
    queue = snap["histograms"]["favor_frontend_queue_seconds"]["series"][""]
    assert queue["count"] == 12
    assert queue["sum"] >= 0.0


class _Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: records each
    annotation's name and metadata."""
    seen: list = []

    def __init__(self, name, **kw):
        self.seen.append((name, kw))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.mark.parametrize("annotate", [True, False])
def test_annotations_carry_the_batch_trace_id(small_index, small_dataset,
                                              monkeypatch, annotate):
    _, _, schema = small_dataset
    monkeypatch.setattr(_Recorder, "seen", [])
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Recorder)

    async def main():
        eng = ServeEngine(LocalBackend(small_index), OPTS, max_batch=8,
                          obs=ObsSpec(kernel_annotations=annotate))
        fe = FrontEnd(eng, FrontEndSpec(coalesce_ms=5.0, coalesce_target=8))
        qs = _queries(8, 16, seed=33)
        await asyncio.gather(*[fe.submit(qs[i], _flt(schema))
                               for i in range(8)])
        traces = list(eng.obs.tracer.traces)
        await fe.close()
        return traces

    traces = asyncio.run(main())
    seen = _Recorder.seen
    if not annotate:
        assert seen == []
        return
    ids = {tr.trace_id for tr in traces}
    names = [name for name, _ in seen]
    assert all(name.startswith("favor.") for name in names)
    for name in ("favor.compile", "favor.estimate/dispatch",
                 "favor.estimate/wait", "favor.fetch",
                 "favor.frontend/dispatch", "favor.frontend/settle"):
        assert name in names, names
    assert ("favor.graph/search" in names) or ("favor.brute/search" in names)
    for name, kw in seen:
        if name == "favor.frontend/dispatch":
            assert kw == {}      # opened before the batch's trace exists
        else:
            assert kw["trace_id"] in ids, (name, kw)
    # every span of every trace was annotated once, under its own trace id
    spans = sorted((f"favor.{sp.path}", tr.trace_id)
                   for tr in traces for sp in tr.walk())
    annotated = sorted((name, kw["trace_id"]) for name, kw in seen
                       if not name.startswith("favor.frontend/"))
    assert annotated == spans


def test_annotated_results_match_obs_off(small_index, small_dataset):
    _, _, schema = small_dataset
    on = ServeEngine(LocalBackend(small_index), OPTS, max_batch=8,
                     obs=ObsSpec(kernel_annotations=True, slow_ms=0.0))
    off = ServeEngine(LocalBackend(small_index), OPTS, max_batch=8,
                      obs=ObsSpec(enabled=False))
    for a, b in zip(_drive(on, schema, n=16, seed=35),
                    _drive(off, schema, n=16, seed=35)):
        assert np.array_equal(a.ids, b.ids)
        assert np.array_equal(a.dists, b.dists)
        assert a.route == b.route and a.p_hat == b.p_hat


def test_xla_compiles_counted_by_function():
    obs = Obs(ObsSpec())
    counter = obs.registry.counter("favor_xla_compiles_total",
                                   labels=("fun",))

    @jax.jit
    def favor_fresh_compile_probe(x):
        return x * 3.0 + 1.0

    favor_fresh_compile_probe(jnp.arange(5.0)).block_until_ready()
    # JAX names the compiled function as it jits it: jit(<name>)
    assert counter.value(fun="jit(favor_fresh_compile_probe)") >= 1
    n = counter.total()
    favor_fresh_compile_probe(jnp.arange(5.0)).block_until_ready()
    assert counter.total() == n          # a cached executable: no compile
    text = obs.prometheus_text()
    assert ('favor_xla_compiles_total{fun="jit(favor_fresh_compile_probe)"}'
            in text)


WAVE_SCOPES = ("graph.init", "wave.select", "wave.visit", "wave.score",
               "wave.filter", "wave.merge", "graph.compact")


@pytest.mark.parametrize("use_pallas", [False, True])
def test_traversal_hlo_carries_every_wave_scope(small_index, use_pallas):
    opts = SearchOptions(k=5, ef=32, use_pallas=use_pallas)
    b = 8
    schema = small_index.attrs.schema
    programs = router.compile_programs(_flt(schema), schema, b)
    lowered = favor_graph_search.lower(
        small_index.g, jnp.asarray(_queries(b, 16)), programs,
        jnp.zeros((b,), jnp.float32), opts.search_config(),
        valid=jnp.ones((b,), bool))
    text = lowered.as_text(debug_info=True)
    for scope in WAVE_SCOPES:
        assert scope in text, scope
        assert "favor." not in scope     # kernels alone carry favor.<name>
    assert "favor.graph_wave" not in text
    assert "favor.graph_traverse" not in text
    if use_pallas:
        # the kernel's own scope stays innermost around its pallas_call:
        # in the compiled program's op metadata it nests inside wave.score
        op_names = re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())
        assert any(re.search(r"/wave\.score/(jit\([^)/]*\)/)?"
                             r"favor\.gather_distance/", n) for n in op_names)


def test_wave_merge_compiles_without_sort_or_gather(small_index):
    """The pool merge is rank-and-select: no sort and no gather of the
    compiled traversal carries the ``wave.merge`` scope, which is there."""
    opts = SearchOptions(k=5, ef=32)
    b = 8
    schema = small_index.attrs.schema
    programs = router.compile_programs(_flt(schema), schema, b)
    text = favor_graph_search.lower(
        small_index.g, jnp.asarray(_queries(b, 16)), programs,
        jnp.zeros((b,), jnp.float32), opts.search_config(),
        valid=jnp.ones((b,), bool)).compile().as_text()
    merge_ops = set()
    for line in text.splitlines():
        name = re.search(r'op_name="([^"]*)"', line)
        if name and "wave.merge/" in name.group(1):
            # "%name = <shape> <opcode>(operands), ..., metadata={...}"
            merge_ops.add(re.match(r"\s*(?:ROOT\s+)?\S+\s*=\s*.*?\s([\w-]+)\(",
                                   line).group(1))
    assert merge_ops, "no compiled op carries wave.merge"
    assert not merge_ops & {"sort", "gather"}, merge_ops


@pytest.mark.parametrize("use_pallas", [False, True])
def test_wave_reads_block_scalars_as_one_rank2_gather(small_index,
                                                      use_pallas):
    """A wave reads its neighbour block's per-row scalars once: no gather
    of the compiled traversal carries the ``wave.filter`` scope, and every
    gather under ``wave.score`` reads a rank-2 operand -- the vector rows
    (N, d) or the packed side rows (N, 1 + mi + mf) -- so XLA has not split
    the side rows back into 1-D gathers of norms and attributes."""
    opts = SearchOptions(k=5, ef=32, use_pallas=use_pallas)
    b = 8
    g = small_index.g
    schema = small_index.attrs.schema
    programs = router.compile_programs(_flt(schema), schema, b)
    text = favor_graph_search.lower(
        g, jnp.asarray(_queries(b, 16)), programs,
        jnp.zeros((b,), jnp.float32), opts.search_config(),
        valid=jnp.ones((b,), bool)).compile().as_text()
    # "%name = <type>[<dims>]{layout} <opcode>(operands), ..., metadata={...}"
    defn = re.compile(r"\s*(?:ROOT\s+)?(%[\w.\-]+)\s*=\s*\w+\[([\d,]*)\]")
    shapes = {}
    for line in text.splitlines():
        m = defn.match(line)
        if m:
            shapes[m.group(1)] = tuple(int(x) for x in m.group(2).split(",")
                                       if x)
    n, dim = g["vectors"].shape
    side = (n, 1 + g["attrs_int"].shape[1] + g["attrs_float"].shape[1])
    read, scopes = set(), set()
    for line in text.splitlines():
        name = re.search(r'op_name="([^"]*)"', line)
        if not name:
            continue
        for scope in ("wave.score", "wave.filter"):
            if f"{scope}/" in name.group(1):
                scopes.add(scope)
        if not re.search(r"\sgather\(", line):
            continue
        assert "wave.filter/" not in name.group(1), line
        if "wave.score/" in name.group(1):
            operand = re.search(r"\sgather\((?:\w+\[[\d,]*\]\S*\s+)?"
                                r"(%[\w.\-]+)", line).group(1)
            read.add(shapes[operand])
    assert scopes == {"wave.score", "wave.filter"}
    assert read == {(n, dim), side}, read
