"""Serving cache subsystem: LruTtlCache policy, canonical-signature keys,
CachingBackend parity over LocalBackend and ShardedBackend (hits and misses
identical to uncached), candidate-block admission, epoch invalidation, and
ServeEngine stats/latency-window accounting."""
import numpy as np
import pytest

import jax

from repro.cache import CachingBackend, LruTtlCache
from repro.core import (BuildSpec, CacheSpec, HnswParams, LocalBackend,
                        SearchOptions, ShardedBackend, paper_filters, router)
from repro.core import filters as F
from repro.serving import ServeEngine


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


# ---------------------------------------------------------------------------
# LRU + TTL container
# ---------------------------------------------------------------------------
def test_lru_evicts_least_recently_used():
    c = LruTtlCache(cap=2)
    c.put("a", 1)
    c.put("b", 2)
    assert c.get("a") == 1          # touch: "b" is now LRU
    c.put("c", 3)
    assert c.get("b") is None and c.get("a") == 1 and c.get("c") == 3
    assert c.evictions == 1


def test_lru_ttl_expires_entries():
    clk = FakeClock()
    c = LruTtlCache(cap=8, ttl_s=10.0, clock=clk)
    c.put("a", 1)
    clk.t = 9.0
    assert c.get("a") == 1
    clk.t = 21.0
    assert c.get("a") is None
    assert c.expirations == 1 and c.misses == 1


def test_lru_validation_and_stats():
    with pytest.raises(ValueError, match="cap"):
        LruTtlCache(cap=0)
    with pytest.raises(ValueError, match="ttl_s"):
        LruTtlCache(cap=1, ttl_s=0)
    c = LruTtlCache(cap=4)
    c.put("a", None)                # None is a legal cached value
    assert "a" in c
    st = c.stats()
    assert st["size"] == 1 and st["cap"] == 4


def test_semantic_ttl_is_per_entry():
    """A hot key receiving fresh inserts must not keep old entries alive:
    entry age, not key age, decides expiry."""
    from repro.cache import SemanticResultCache
    clk = FakeClock()
    cache = SemanticResultCache(CacheSpec(ttl_s=10.0), clock=clk)
    opts = SearchOptions(k=2)
    old_q = np.zeros((4,), np.float32)
    cache.put("sig", opts, old_q, [1, 2], [0.1, 0.2], 0.5, False)
    for step in range(1, 5):                    # keep the key hot past TTL
        clk.t = 4.0 * step
        q = np.full((4,), float(step), np.float32)
        cache.put("sig", opts, q, [1, 2], [0.1, 0.2], 0.5, False)
    assert clk.t == 16.0                        # old entry is past its TTL
    assert cache.get("sig", opts, old_q) is None
    assert cache.get("sig", opts, np.full((4,), 4.0, np.float32)) is not None


def test_cache_spec_validation():
    with pytest.raises(ValueError, match="selectivity_cap"):
        CacheSpec(selectivity_cap=0)
    with pytest.raises(ValueError, match="candidate_p_max"):
        CacheSpec(candidate_p_max=1.5)
    with pytest.raises(ValueError, match="ttl_s"):
        CacheSpec(ttl_s=-1.0)
    assert CacheSpec().with_(semantic=False).semantic is False


# ---------------------------------------------------------------------------
# canonical signatures as cache keys
# ---------------------------------------------------------------------------
def test_signatures_shared_across_equivalent_filters(small_dataset):
    _, _, schema = small_dataset
    a = F.And(F.Equality("i0", 3), F.Range("f0", 10, 20))
    commuted = F.And(F.Range("f0", 10, 20), F.Equality("i0", 3))
    double_neg = F.Not(F.Not(a))
    dup_disjunct = F.Or(a, a)
    sig = F.filter_signature(a, schema)
    assert F.filter_signature(commuted, schema) == sig
    assert F.filter_signature(double_neg, schema) == sig
    assert F.filter_signature(dup_disjunct, schema) == sig
    assert F.filter_signature(F.Equality("i0", 3), schema) != sig
    # batch signatures match the scalar path
    progs = router.compile_programs([a, commuted], schema, 2)
    assert F.batch_signatures(progs) == [sig, sig]


# ---------------------------------------------------------------------------
# CachingBackend over LocalBackend
# ---------------------------------------------------------------------------
@pytest.fixture()
def cached_local(small_index):
    return CachingBackend(LocalBackend(small_index), CacheSpec())


def test_caching_backend_parity_cold_and_warm(cached_local, small_index,
                                              small_dataset):
    vecs, _, schema = small_dataset
    base = LocalBackend(small_index)
    rng = np.random.default_rng(50)
    qs = rng.normal(size=(6, vecs.shape[1])).astype(np.float32)
    opts = SearchOptions(k=10, ef=64)
    for name, flt in paper_filters(schema).items():
        r0 = router.execute(base, qs, flt, opts)
        cold = router.execute(cached_local, qs, flt, opts)
        warm = router.execute(cached_local, qs, flt, opts)
        np.testing.assert_array_equal(r0.ids, cold.ids, err_msg=name)
        np.testing.assert_array_equal(r0.ids, warm.ids, err_msg=name)
        np.testing.assert_array_equal(r0.routed_brute, warm.routed_brute,
                                      err_msg=name)
        np.testing.assert_allclose(r0.p_hat, warm.p_hat, err_msg=name)
    st = cached_local.cache_stats()
    assert st["semantic"]["hits"] > 0          # warm pass was served cached
    assert st["selectivity"]["size"] > 0


def test_selectivity_cache_skips_inner_estimate(cached_local, small_dataset):
    _, _, schema = small_dataset
    flt = paper_filters(schema)["equality_bool"]
    progs = router.compile_programs([flt] * 4, schema, 4)
    calls = []
    inner_estimate = cached_local.inner.estimate
    cached_local.inner.estimate = lambda p: calls.append(1) or inner_estimate(p)
    try:
        p0 = cached_local.estimate(progs)
        p1 = cached_local.estimate(progs)
    finally:
        cached_local.inner.estimate = inner_estimate
    # 4 identical programs -> one inner call row on the cold pass, zero warm
    assert len(calls) == 1
    np.testing.assert_array_equal(p0, p1)
    st = cached_local.cache_stats()["selectivity"]
    assert st["hits"] == 4 and st["misses"] == 4


def test_candidate_cache_admits_on_second_reference(cached_local, small_index,
                                                    small_dataset):
    vecs, attrs, schema = small_dataset
    base = LocalBackend(small_index)
    # a low-selectivity filter that routes brute under the default lambda
    flt = F.And(F.Equality("i0", 2), F.Range("f0", 5.0, 15.0))
    sel = float(F.eval_program(F.compile_filter(flt, schema), attrs.ints,
                               attrs.floats).mean())
    assert sel < 0.02
    opts = SearchOptions(k=10, ef=64, force="brute")
    rng = np.random.default_rng(51)
    for round_ in range(3):
        # fresh query vectors each round: only the candidate layer can hit
        qs = rng.normal(size=(4, vecs.shape[1])).astype(np.float32)
        rc = router.execute(cached_local, qs, flt, opts)
        rb = router.execute(base, qs, flt, opts)
        np.testing.assert_array_equal(rc.ids, rb.ids, err_msg=f"round {round_}")
        np.testing.assert_allclose(rc.dists, rb.dists, rtol=1e-5, atol=1e-5)
    st = cached_local.cache_stats()["candidates"]
    assert st["size"] == 1          # admitted after the second brute miss
    assert st["hits"] >= 1          # third round scanned the cached block


def test_candidate_cache_respects_p_max_gate(small_index, small_dataset):
    vecs, _, schema = small_dataset
    cb = CachingBackend(LocalBackend(small_index),
                        CacheSpec(candidate_p_max=0.001, semantic=False))
    flt = F.And(F.Equality("i0", 2), F.Range("f0", 5.0, 15.0))  # ~1% > gate
    opts = SearchOptions(k=10, ef=64, force="brute")
    rng = np.random.default_rng(52)
    for _ in range(3):
        qs = rng.normal(size=(2, vecs.shape[1])).astype(np.float32)
        router.execute(cb, qs, flt, opts)
    st = cb.cache_stats()["candidates"]
    assert st["size"] == 0 and st["bypasses"] >= 1


def test_epoch_bump_invalidates_all_layers(cached_local, small_index,
                                           small_dataset):
    vecs, _, schema = small_dataset
    flt = paper_filters(schema)["logic"]
    rng = np.random.default_rng(53)
    qs = rng.normal(size=(4, vecs.shape[1])).astype(np.float32)
    opts = SearchOptions(k=10, ef=64)
    r0 = router.execute(cached_local, qs, flt, opts)
    router.execute(cached_local, qs, flt, opts)   # warm the layers
    assert cached_local.cache_stats()["semantic"]["size"] > 0
    small_index.bump_version()
    r1 = router.execute(cached_local, qs, flt, opts)
    assert cached_local.invalidations == 1
    assert cached_local.version() == small_index.version()
    # stale entries were dropped, recomputed results are identical
    np.testing.assert_array_equal(r0.ids, r1.ids)


@pytest.mark.parametrize("threshold", [0.5, 0.0])
def test_semantic_threshold_serves_near_duplicates(small_index, small_dataset,
                                                   threshold):
    """Threshold 0 serves exact repeats only; a larger radius serves the
    same stream's near duplicates too, never fewer hits."""
    vecs, _, schema = small_dataset
    cb = CachingBackend(LocalBackend(small_index),
                        CacheSpec(semantic_threshold=threshold,
                                  candidates=False))
    flt = paper_filters(schema)["equality_bool"]
    opts = SearchOptions(k=5, ef=48)
    rng = np.random.default_rng(54)
    q = rng.normal(size=(1, vecs.shape[1])).astype(np.float32)
    r0 = router.execute(cb, q, flt, opts)
    jitter = q + (0.1 / np.sqrt(vecs.shape[1])).astype(np.float32)
    r1 = router.execute(cb, jitter, flt, opts)
    if threshold:                                  # within threshold
        np.testing.assert_array_equal(r0.ids, r1.ids)  # served from cache
        assert cb.cache_stats()["semantic"]["hits"] == 1
    else:                                          # not a repeat: a miss
        assert cb.cache_stats()["semantic"]["hits"] == 0
        np.testing.assert_array_equal(
            r1.ids, router.execute(LocalBackend(small_index), jitter, flt,
                                   opts).ids)
    far = q + 10.0
    router.execute(cb, far, flt, opts)             # outside threshold: miss
    assert cb.cache_stats()["semantic"]["misses"] >= 2
    router.execute(cb, q, flt, opts)               # an exact repeat: a hit
    assert cb.cache_stats()["semantic"]["hits"] == 1 + (threshold > 0)


def test_disabled_layers_bypass(small_index, small_dataset):
    vecs, _, schema = small_dataset
    spec = CacheSpec(selectivity=False, candidates=False, semantic=False)
    cb = CachingBackend(LocalBackend(small_index), spec)
    flt = paper_filters(schema)["equality_int"]
    qs = np.zeros((2, vecs.shape[1]), np.float32)
    opts = SearchOptions(k=5, ef=48)
    progs = router.compile_programs([flt] * 2, schema, 2)
    assert cb.lookup_result(qs, progs, opts) is None
    r = router.execute(cb, qs, flt, opts)
    assert r.ids.shape == (2, 5)
    st = cb.cache_stats()
    assert st["selectivity"]["hits"] == st["semantic"]["hits"] == 0
    assert st["selectivity"]["bypasses"] > 0


# ---------------------------------------------------------------------------
# CachingBackend over ShardedBackend (1-device mesh)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def sharded_1dev(small_dataset):
    vecs, attrs, _ = small_dataset
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    return ShardedBackend.build(vecs, attrs, mesh,
                                BuildSpec(hnsw=HnswParams(M=8, efc=48, seed=3)))


def test_caching_backend_wraps_sharded(sharded_1dev, small_dataset):
    vecs, _, schema = small_dataset
    cb = CachingBackend(sharded_1dev, CacheSpec())
    rng = np.random.default_rng(55)
    qs = rng.normal(size=(4, vecs.shape[1])).astype(np.float32)
    opts = SearchOptions(k=10, ef=64)
    for flt in (paper_filters(schema)["equality_int"],
                F.And(F.Equality("i0", 2), F.Range("f0", 5.0, 15.0))):
        r0 = router.execute(sharded_1dev, qs, flt, opts)
        cold = router.execute(cb, qs, flt, opts)
        warm = router.execute(cb, qs, flt, opts)
        np.testing.assert_array_equal(r0.ids, cold.ids)
        np.testing.assert_array_equal(r0.ids, warm.ids)
    # candidate layer found the sharded corpus view
    assert cb._corpus() is not None
    sharded_1dev.bump_version()
    r1 = router.execute(cb, qs, paper_filters(schema)["equality_int"], opts)
    assert cb.invalidations == 1 and r1.ids.shape == (4, 10)


# ---------------------------------------------------------------------------
# ServeEngine surfacing
# ---------------------------------------------------------------------------
def test_engine_surfaces_cache_stats_and_bounds_latencies(small_index,
                                                          small_dataset):
    vecs, _, schema = small_dataset
    cb = CachingBackend(LocalBackend(small_index), CacheSpec())
    eng = ServeEngine(cb, SearchOptions(k=5, ef=48), max_batch=8,
                      max_wait_ms=1e6, latency_window=8)
    rng = np.random.default_rng(56)
    qs = rng.normal(size=(8, vecs.shape[1])).astype(np.float32)
    flt = paper_filters(schema)["equality_bool"]
    for _ in range(3):                      # 24 requests, window of 8
        for i in range(8):
            eng.submit(qs[i], flt)
        eng.run()
    assert len(eng.latencies) == 8          # rolling window, not append-only
    st = eng.stats
    assert st["graph"] + st["brute"] == 24
    assert st["cache"]["semantic"]["hits"] >= 8   # repeat rounds were cached
    eng.reset_stats()
    assert eng.stats["batches"] == 0 and len(eng.latencies) == 0
    # cache contents survive an engine stats reset
    assert eng.stats["cache"]["semantic"]["size"] > 0
    with pytest.raises(ValueError, match="latency_window"):
        ServeEngine(cb, SearchOptions(), latency_window=0)
