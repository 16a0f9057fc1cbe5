"""Async multi-tenant front-end semantics (repro.serving.frontend).

Acceptance bars from the PR-7 issue:
  * coalescing preserves bit-identical results vs. one-shot batches
  * shed requests never reach the backend
  * per-tenant cache isolation (A's semantic/candidate hits never serve B)
  * clean cancellation of in-flight futures on shutdown
plus the satellite contracts: the engine bucket ladder unified on
BatchSpec, deadline-aware ``run()`` and the ``drain()`` helper.

No pytest-asyncio: every async scenario runs through ``asyncio.run`` so the
dev extras stay unchanged.
"""
import asyncio
import time

import numpy as np
import pytest

from repro.cache import CachingBackend
from repro.core import (BatchSpec, CacheSpec, FrontEndSpec, LocalBackend,
                        SearchOptions, TenantSpec, router)
from repro.core import filters as F
from repro.serving import FrontEnd, Overloaded, ServeEngine
from repro.serving.engine import _bucket
from repro.serving.frontend import TokenBucket, WeightedFairScheduler
from repro.serving.frontend.admission import TenantState

OPTS = SearchOptions(k=5, ef=48, batch=BatchSpec(min_bucket=4, max_bucket=16))


def _queries(n, d, seed=0):
    return np.random.default_rng(seed).normal(size=(n, d)).astype(np.float32)


def _flt(schema):
    return F.paper_filters(schema)["equality_bool"]


# ---------------------------------------------------------------------------
# Spec validation
# ---------------------------------------------------------------------------
def test_tenant_spec_validation():
    TenantSpec(weight=2.0, rate_qps=100.0, burst=4, queue_cap=8,
               deadline_ms=50.0)
    with pytest.raises(ValueError, match="weight"):
        TenantSpec(weight=0.0)
    with pytest.raises(ValueError, match="rate_qps"):
        TenantSpec(rate_qps=-1.0)
    with pytest.raises(ValueError, match="burst"):
        TenantSpec(burst=0)
    with pytest.raises(ValueError, match="queue_cap"):
        TenantSpec(queue_cap=0)
    with pytest.raises(ValueError, match="deadline_ms"):
        TenantSpec(deadline_ms=0.0)


def test_frontend_spec_validation_and_tenant_lookup():
    spec = FrontEndSpec(coalesce_ms=5.0,
                        tenants={"b": TenantSpec(weight=2.0),
                                 "a": TenantSpec(weight=3.0)})
    # dict canonicalizes to a sorted tuple (frozen, deterministic)
    assert spec.tenants[0][0] == "a"
    assert spec.tenant("b").weight == 2.0
    assert spec.tenant("nope") == spec.default_tenant
    with pytest.raises(ValueError, match="coalesce_ms"):
        FrontEndSpec(coalesce_ms=-1.0)
    with pytest.raises(ValueError, match="coalesce_target"):
        FrontEndSpec(coalesce_target=0)
    with pytest.raises(TypeError, match="tenants"):
        FrontEndSpec(tenants={"a": 1.0})
    with pytest.raises(TypeError, match="default_tenant"):
        FrontEndSpec(default_tenant="gold")


# ---------------------------------------------------------------------------
# Admission primitives (no engine, fake clocks)
# ---------------------------------------------------------------------------
def test_token_bucket_rate_and_burst():
    t = [0.0]
    b = TokenBucket(10.0, 2, clock=lambda: t[0])
    assert b.try_take() and b.try_take()       # burst of 2
    assert not b.try_take()                    # empty
    assert b.retry_after_s() == pytest.approx(0.1)
    t[0] += 0.1                                # one token refilled
    assert b.try_take() and not b.try_take()
    t[0] += 10.0                               # refill clamps at burst
    assert b.tokens <= 2.0
    assert b.try_take() and b.try_take() and not b.try_take()
    with pytest.raises(ValueError, match="rate_qps"):
        TokenBucket(0.0, 2)


def test_weighted_fair_dequeue_shares_and_no_starvation():
    sched = WeightedFairScheduler()
    heavy = TenantState("heavy", TenantSpec(weight=3.0), 1, None)
    light = TenantState("light", TenantSpec(weight=1.0), 2, None)
    for st in (heavy, light):
        for i in range(40):
            sched.on_enqueue(st)
            st.queue.append(i)
    order = []
    for _ in range(40):
        st = sched.pick([heavy, light])
        st.queue.popleft()
        sched.on_dequeue(st)
        order.append(st.name)
    # ~3:1 split over the first 40 slots, and the light tenant is never
    # starved out of a window
    assert 25 <= order.count("heavy") <= 35
    assert order.count("light") >= 5
    assert "light" in order[:8]


# ---------------------------------------------------------------------------
# Engine satellites: unified ladder, deadline-aware run(), drain()
# ---------------------------------------------------------------------------
def test_bucket_unified_with_batchspec_ladder():
    # the legacy helper and BatchSpec agree on every size: one ladder
    for n in (1, 7, 8, 9, 100, 512, 513, 2000):
        assert _bucket(n) == BatchSpec().bucket_for(n)
    spec = BatchSpec(min_bucket=4, max_bucket=8)
    assert _bucket(3, spec) == 4 and _bucket(9, spec) == 16


def test_engine_pad_spec_follows_opts(small_index):
    eng = ServeEngine(LocalBackend(small_index), OPTS)
    assert eng.pad_spec is OPTS.batch
    eng2 = ServeEngine(LocalBackend(small_index), SearchOptions(k=5, ef=48))
    assert eng2.pad_spec == BatchSpec()       # default ladder == old hardcode


def test_run_waits_out_straggler_deadline(small_index, small_dataset):
    _, _, schema = small_dataset
    eng = ServeEngine(LocalBackend(small_index), OPTS, max_batch=8,
                      max_wait_ms=120.0)
    q = _queries(1, 16, seed=3)[0]
    eng.submit(q, _flt(schema))
    eng.drain()                               # absorb compile time first
    eng.submit(q, _flt(schema))
    t0 = time.perf_counter()
    out = eng.run()
    waited = time.perf_counter() - t0
    assert len(out) == 1 and not eng.queue
    assert waited >= 0.1                      # honored the coalescing window


def test_drain_forces_immediately(small_index, small_dataset):
    _, _, schema = small_dataset
    eng = ServeEngine(LocalBackend(small_index), OPTS, max_batch=8,
                      max_wait_ms=1e6)
    q = _queries(1, 16, seed=4)[0]
    eng.submit(q, _flt(schema))
    out = eng.drain()                         # would hang under run()
    assert len(out) == 1 and not eng.queue


def test_serve_engine(small_index, small_dataset):
    vecs, attrs, schema = small_dataset
    eng = ServeEngine(LocalBackend(small_index), SearchOptions(k=5, ef=48),
                      max_batch=16)
    flts = F.paper_filters(schema)
    rng = np.random.default_rng(0)
    rids = []
    for i in range(40):
        q = rng.normal(size=(vecs.shape[1],)).astype(np.float32)
        name = list(flts)[i % len(flts)]
        rids.append(eng.submit(q, flts[name]))
    out = eng.run()
    assert len(out) == 40
    assert sorted(r.rid for r in out) == sorted(rids)
    assert eng.stats["graph"] + eng.stats["brute"] == 40
    pct = eng.latency_percentiles()
    assert pct["p50"] <= pct["p99"]


def test_serve_engine_deadline(small_index, small_dataset):
    """max_wait_ms is honored: a partial batch waits for the deadline, a full
    batch flushes immediately, and run(until_empty=) is wired."""
    vecs, attrs, schema = small_dataset
    eng = ServeEngine(LocalBackend(small_index), SearchOptions(k=5, ef=48),
                      max_batch=8, max_wait_ms=1e6)
    flts = list(F.paper_filters(schema).values())
    rng = np.random.default_rng(1)

    def submit(n):
        for i in range(n):
            q = rng.normal(size=(vecs.shape[1],)).astype(np.float32)
            eng.submit(q, flts[i % len(flts)])

    # partial batch, deadline far in the future -> engine keeps waiting
    submit(3)
    assert eng.step() == []
    assert eng.run(until_empty=False) == []
    assert len(eng.queue) == 3

    # oldest request past the deadline -> the partial batch flushes
    eng.queue[0].t_submit -= 2 * eng.max_wait_s
    out = eng.step()
    assert len(out) == 3 and not eng.queue

    # full batch flushes immediately despite the huge deadline
    submit(8)
    assert len(eng.step()) == 8

    # drain() forces out partial batches immediately, even with the huge
    # deadline (run(until_empty=True) would wait the straggler window out)
    submit(3)
    assert len(eng.drain()) == 3 and not eng.queue


# ---------------------------------------------------------------------------
# Front-end: coalescing parity + pad reduction
# ---------------------------------------------------------------------------
def test_coalescing_bit_identical_to_one_shot_batch(small_index,
                                                    small_dataset):
    _, _, schema = small_dataset
    backend = LocalBackend(small_index)
    flts = list(F.paper_filters(schema).values())[:4]
    qs = _queries(8, 16, seed=11)
    reqs = [(qs[i], flts[i % len(flts)]) for i in range(8)]

    ref = router.execute(backend, qs, [f for _, f in reqs], OPTS)

    async def main():
        eng = ServeEngine(backend, OPTS, max_batch=16)
        fe = FrontEnd(eng, FrontEndSpec(coalesce_ms=500.0, coalesce_target=8))
        outs = await asyncio.gather(*[fe.submit(q, f) for q, f in reqs])
        st = fe.stats
        await fe.close()
        return outs, st

    outs, st = asyncio.run(main())
    # one coalesced dispatch, results bit-identical to the one-shot batch
    assert st["coalesce"]["dispatches"] == 1
    assert st["coalesce"]["mean_batch"] == 8.0
    for i, r in enumerate(outs):
        assert np.array_equal(r.ids, ref.ids[i])
        assert np.array_equal(r.dists, ref.dists[i])
        assert r.route == ("brute" if ref.routed_brute[i] else "graph")


def test_coalescing_cuts_pad_overhead(small_index, small_dataset):
    """The acceptance direction: at one-at-a-time arrival, an uncoalesced
    front-end pads every single-row dispatch to the smallest bucket while
    a coalesced one fills the bucket first."""
    _, _, schema = small_dataset
    flt = _flt(schema)
    qs = _queries(4, 16, seed=12)

    async def drive(spec):
        eng = ServeEngine(LocalBackend(small_index), OPTS, max_batch=16)
        eng.warmup()
        fe = FrontEnd(eng, spec)
        if spec.coalesce_ms:
            await asyncio.gather(*[fe.submit(q, flt) for q in qs])
        else:
            for q in qs:                     # arrivals one dispatch apart
                await fe.submit(q, flt)
        pad = fe.stats["engine"]["batching"]["pad_overhead"]
        await fe.close()
        return pad, fe

    pad_un, _ = asyncio.run(drive(FrontEndSpec(coalesce_ms=0.0)))
    pad_co, _ = asyncio.run(drive(FrontEndSpec(coalesce_ms=500.0,
                                               coalesce_target=4)))
    assert pad_un >= 0.7                      # 1 real row per 4-row bucket
    assert pad_co < pad_un


# ---------------------------------------------------------------------------
# Admission control: shed at the door, never the backend
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("cold", [False, True])
def test_shed_requests_never_reach_backend(small_index, small_dataset, cold):
    """``cold``: a second tenant submits while the first is being shed; its
    request is served and no shed is charged to it."""
    _, _, schema = small_dataset
    flt = _flt(schema)
    qs = _queries(4, 16, seed=13)

    async def main():
        eng = ServeEngine(LocalBackend(small_index), OPTS, max_batch=16)
        spec = FrontEndSpec(coalesce_ms=1e4, coalesce_target=64,
                            tenants={"t": TenantSpec(queue_cap=1),
                                     "c": TenantSpec(queue_cap=1)})
        fe = FrontEnd(eng, spec)
        t1 = asyncio.create_task(fe.submit(qs[0], flt, tenant="t"))
        tc = (asyncio.create_task(fe.submit(qs[3], flt, tenant="c"))
              if cold else None)
        await asyncio.sleep(0.02)             # t1 is queued (held window)
        shed = []
        for i in (1, 2):
            with pytest.raises(Overloaded) as e:
                await fe.submit(qs[i], flt, tenant="t")
            shed.append(e.value.reason)
        await fe.close(drain=True)            # serves only the queued ones
        rc = await tc if cold else None
        return await t1, rc, shed, fe.stats

    r1, rc, shed, st = asyncio.run(main())
    assert shed == ["queue_full", "queue_full"]
    t = st["tenants"]["t"]
    assert t["served"] == 1 and t["shed"]["queue_full"] == 2
    assert t["shed_total"] == 2
    # the backend saw exactly the served requests, nothing shed
    assert st["engine"]["graph"] + st["engine"]["brute"] == 1 + cold
    assert r1.ids.shape == (5,)
    if cold:
        c = st["tenants"]["c"]
        assert c["served"] == 1 and c["shed_total"] == 0
        assert rc.ids.shape == (5,)


def test_rate_limit_shed_with_retry_after(small_index, small_dataset):
    _, _, schema = small_dataset
    flt = _flt(schema)
    q = _queries(1, 16, seed=14)[0]

    async def main():
        eng = ServeEngine(LocalBackend(small_index), OPTS, max_batch=16)
        spec = FrontEndSpec(
            tenants={"t": TenantSpec(rate_qps=0.001, burst=1)})
        fe = FrontEnd(eng, spec)
        r = await fe.submit(q, flt, tenant="t")
        with pytest.raises(Overloaded) as e:
            await fe.submit(q, flt, tenant="t")
        await fe.close()
        return r, e.value

    r, err = asyncio.run(main())
    assert err.reason == "rate_limit" and err.tenant == "t"
    assert err.retry_after_ms is not None and err.retry_after_ms > 0
    assert r.ids.shape == (5,)


def test_admission_off_is_unbounded_fifo(small_index, small_dataset):
    _, _, schema = small_dataset
    flt = _flt(schema)
    qs = _queries(4, 16, seed=15)

    async def main():
        eng = ServeEngine(LocalBackend(small_index), OPTS, max_batch=16)
        spec = FrontEndSpec(admission=False, fair=False, coalesce_ms=200.0,
                            coalesce_target=4,
                            tenants={"t": TenantSpec(queue_cap=1,
                                                     rate_qps=0.001)})
        fe = FrontEnd(eng, spec)
        outs = await asyncio.gather(*[fe.submit(q, flt, tenant="t")
                                      for q in qs])
        st = fe.stats
        await fe.close()
        return outs, st

    outs, st = asyncio.run(main())
    assert len(outs) == 4
    assert st["tenants"]["t"]["shed_total"] == 0


def test_deadline_shed(small_index, small_dataset):
    _, _, schema = small_dataset
    flt = _flt(schema)
    q = _queries(1, 16, seed=16)[0]

    async def main():
        eng = ServeEngine(LocalBackend(small_index), OPTS, max_batch=16)
        fe = FrontEnd(eng, FrontEndSpec(coalesce_ms=1e4, coalesce_target=64))
        task = asyncio.create_task(fe.submit(q, flt, deadline_ms=5.0))
        await asyncio.sleep(0.05)             # deadline lapses while held
        with pytest.raises(Overloaded) as e:
            await task
        st = fe.stats
        await fe.close()
        return e.value, st

    err, st = asyncio.run(main())
    assert err.reason == "deadline"
    assert st["tenants"]["default"]["shed"]["deadline"] == 1
    assert st["engine"]["graph"] + st["engine"]["brute"] == 0


# ---------------------------------------------------------------------------
# Tenant-scoped caches: isolation
# ---------------------------------------------------------------------------
def test_semantic_cache_isolated_per_tenant(small_index, small_dataset):
    _, _, schema = small_dataset
    flt = _flt(schema)
    q = _queries(1, 16, seed=17)[0]

    async def main():
        cb = CachingBackend(LocalBackend(small_index), CacheSpec())
        eng = ServeEngine(cb, OPTS, max_batch=16)
        fe = FrontEnd(eng, FrontEndSpec())
        ra1 = await fe.submit(q, flt, tenant="A")
        ra2 = await fe.submit(q, flt, tenant="A")   # exact repeat: A hits
        rb1 = await fe.submit(q, flt, tenant="B")   # B must NOT see A's entry
        st = fe.stats
        await fe.close()
        return (ra1, ra2, rb1), st

    (ra1, ra2, rb1), st = asyncio.run(main())
    a, b = st["tenants"]["A"], st["tenants"]["B"]
    assert a["semantic"]["hits"] == 1 and a["semantic"]["misses"] == 1
    assert b["semantic"]["hits"] == 0 and b["semantic"]["misses"] == 1
    assert a["scope"] != b["scope"] != 0
    # isolation never changes results: all three are the same exact answer
    assert np.array_equal(ra1.ids, ra2.ids)
    assert np.array_equal(ra1.ids, rb1.ids)


def test_candidate_cache_isolated_per_tenant(small_index, small_dataset):
    _, _, schema = small_dataset
    # a filter the selector sends brute; p_max=1.0 admits it regardless
    flt = F.And(F.Equality("i0", 3), F.Range("f0", 10.0, 12.0))
    qs = _queries(3, 16, seed=18)

    async def main():
        cb = CachingBackend(LocalBackend(small_index),
                            CacheSpec(candidate_p_max=1.0, semantic=False))
        eng = ServeEngine(cb, OPTS.with_(force="brute"), max_batch=16)
        fe = FrontEnd(eng, FrontEndSpec())
        for i in range(3):                    # miss, miss(admit), hit for A
            await fe.submit(qs[i], flt, tenant="A")
        await fe.submit(qs[0], flt, tenant="B")   # B: isolated -> miss
        st = fe.stats
        await fe.close()
        return st

    st = asyncio.run(main())
    a, b = st["tenants"]["A"], st["tenants"]["B"]
    assert a["candidates"]["hits"] == 1 and a["candidates"]["misses"] == 2
    assert b["candidates"]["hits"] == 0 and b["candidates"]["misses"] == 1


def test_unscoped_engine_traffic_stays_scope_zero(small_index,
                                                  small_dataset):
    """Direct ServeEngine.submit (no front-end) records under scope 0 --
    the tenant scopes never leak into unscoped traffic."""
    _, _, schema = small_dataset
    flt = _flt(schema)
    q = _queries(1, 16, seed=19)[0]
    cb = CachingBackend(LocalBackend(small_index), CacheSpec())
    eng = ServeEngine(cb, OPTS, max_batch=16)
    eng.submit(q, flt)
    eng.drain()
    eng.submit(q, flt)
    out = eng.drain()
    assert len(out) == 1
    sem = cb.cache_stats()["semantic"]["by_scope"]
    assert set(sem) == {0} and sem[0]["hits"] == 1


# ---------------------------------------------------------------------------
# Shutdown semantics
# ---------------------------------------------------------------------------
def test_close_cancels_in_flight_futures(small_index, small_dataset):
    _, _, schema = small_dataset
    flt = _flt(schema)
    qs = _queries(3, 16, seed=20)

    async def main():
        eng = ServeEngine(LocalBackend(small_index), OPTS, max_batch=16)
        fe = FrontEnd(eng, FrontEndSpec(coalesce_ms=1e4, coalesce_target=64))
        tasks = [asyncio.create_task(fe.submit(q, flt)) for q in qs]
        await asyncio.sleep(0.02)             # all three queued, held
        await fe.close(drain=False)
        cancelled = 0
        for t in tasks:
            try:
                await t
            except asyncio.CancelledError:
                cancelled += 1
        # closed front-end rejects new work with a structured response
        with pytest.raises(Overloaded, match="closed"):
            await fe.submit(qs[0], flt)
        return cancelled, fe.stats

    cancelled, st = asyncio.run(main())
    assert cancelled == 3
    assert st["engine"]["graph"] + st["engine"]["brute"] == 0


def test_close_drain_serves_queued(small_index, small_dataset):
    _, _, schema = small_dataset
    flt = _flt(schema)
    qs = _queries(3, 16, seed=21)

    async def main():
        eng = ServeEngine(LocalBackend(small_index), OPTS, max_batch=16)
        fe = FrontEnd(eng, FrontEndSpec(coalesce_ms=1e4, coalesce_target=64))
        tasks = [asyncio.create_task(fe.submit(q, flt)) for q in qs]
        await asyncio.sleep(0.02)
        await fe.close(drain=True)
        return await asyncio.gather(*tasks)

    outs = asyncio.run(main())
    assert len(outs) == 3 and all(r.ids.shape == (5,) for r in outs)


# ---------------------------------------------------------------------------
# Multiple logical front-ends over one backend
# ---------------------------------------------------------------------------
def test_two_frontends_share_one_backend(small_index, small_dataset):
    _, _, schema = small_dataset
    flt = _flt(schema)
    q = _queries(1, 16, seed=22)[0]

    async def main():
        cb = CachingBackend(LocalBackend(small_index), CacheSpec())
        fe1 = FrontEnd(ServeEngine(cb, OPTS, max_batch=16), FrontEndSpec())
        fe2 = FrontEnd(ServeEngine(cb, OPTS, max_batch=16), FrontEndSpec())
        await fe1.submit(q, flt, tenant="shared")
        r2 = await fe2.submit(q, flt, tenant="shared")
        st1, st2 = fe1.stats, fe2.stats
        await fe1.close()
        await fe2.close()
        return r2, st1, st2

    r2, st1, st2 = asyncio.run(main())
    # the tenant name interns to ONE scope on the shared backend, so the
    # second front-end's identical request is a semantic hit
    assert st1["tenants"]["shared"]["scope"] == \
        st2["tenants"]["shared"]["scope"]
    assert st2["tenants"]["shared"]["semantic"]["hits"] == 1
    assert r2.ids.shape == (5,)
