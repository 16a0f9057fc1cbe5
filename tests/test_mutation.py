"""Live index subsystem: delta segments, tombstones, scoped epochs, merge.

Covers the PR acceptance matrix: empty-delta bit-parity on local / sharded /
caching backends, upsert-is-found / delete-is-gone on every route (including
warm candidate and semantic caches), scoped epoch invalidation (vector-only
upsert keeps the selectivity cache warm), merge equivalence against exact
ground truth, the graph_arrays no-re-upload regression, quantization
persistence, the bulk-build recall bound, and the index edge cases the
mutation path exposes (empty / single-element / delete-everything /
delta-only).
"""
import numpy as np
import pytest

import jax

from repro.cache import CachingBackend
from repro.core import (BuildSpec, FavorIndex, HnswParams, LocalBackend,
                        QuantSpec, SearchOptions, ShardedBackend,
                        paper_schema, random_attributes, router)
from repro.core import filters as F
from repro.core.options import CacheSpec
from repro.index import ComponentEpochs, DeltaSegment, compose_topk
from repro.index.bulk import build_hnsw_bulk
from repro.serving import ServeEngine

OPTS = SearchOptions(k=10, ef=64)


@pytest.fixture(scope="module")
def ds():
    rng = np.random.default_rng(21)
    n, d = 768, 16
    vecs = rng.normal(size=(n, d)).astype(np.float32)
    schema = paper_schema()
    attrs = random_attributes(schema, n, seed=13)
    return vecs, attrs, schema


def _fresh_local(ds):
    vecs, attrs, _ = ds
    return LocalBackend(FavorIndex.build(
        vecs, attrs, HnswParams(M=8, efc=48, seed=3)))


def _exact_topk(vecs, queries, rows, k):
    """Host ground-truth top-k of ``queries`` over the ``rows`` subset."""
    ids = np.full((len(queries), k), -1, np.int64)
    ds_ = np.full((len(queries), k), np.inf, np.float32)
    if len(rows) == 0:
        return ids, ds_
    sub = vecs[rows]
    d = np.sqrt(np.maximum(
        np.sum(queries ** 2, 1)[:, None] + np.sum(sub ** 2, 1)[None, :]
        - 2.0 * queries @ sub.T, 0.0)).astype(np.float32)
    kk = min(k, len(rows))
    order = np.argsort(d, axis=1, kind="stable")[:, :kk]
    ids[:, :kk] = np.asarray(rows)[order]
    ds_[:, :kk] = np.take_along_axis(d, order, axis=1)
    return ids, ds_


def _matching_attrs(attrs, schema, value=3, count=1):
    """Attribute rows copied from a base row with i0 == value."""
    col = schema.int_index("i0")
    row = int(np.nonzero(attrs.ints[:, col] == value)[0][0])
    return (np.tile(attrs.ints[row], (count, 1)),
            np.tile(attrs.floats[row], (count, 1)))


# ---------------------------------------------------------------------------
# building blocks: epochs, delta segment, top-k composition
# ---------------------------------------------------------------------------
def test_component_epochs():
    e = ComponentEpochs()
    assert e.total == 0
    e.bump("vectors")
    e.bump("vectors", "graph")
    assert e.as_dict() == {"vectors": 2, "attributes": 0, "graph": 1}
    assert e.total == 3
    with pytest.raises(ValueError, match="unknown"):
        e.bump("codes")
    e.bump_all()
    assert e.as_dict() == {"vectors": 3, "attributes": 1, "graph": 2}


def test_delta_segment_growth_and_kill():
    d = DeltaSegment(4, 2, 1, min_capacity=4)
    rng = np.random.default_rng(0)
    v = rng.normal(size=(9, 4)).astype(np.float32)
    slots = d.append(v[:3], np.zeros((3, 2), np.int32),
                     np.zeros((3, 1), np.float32),
                     np.arange(100, 103))
    assert list(slots) == [0, 1, 2] and d._cap == 4
    d.append(v[3:], np.zeros((6, 2), np.int32), np.zeros((6, 1), np.float32),
             np.arange(103, 109))
    assert d.count == 9 and d._cap == 16          # pow-2 growth
    assert d.kill(101) and not d.kill(101)        # second kill: already dead
    assert not d.kill(999)
    assert d.live_count == 8 and d.has(100) and not d.has(101)


def test_compose_topk_merge_and_ties():
    bi = np.array([[5, 7, -1]], np.int64)
    bd = np.array([[1.0, 3.0, np.inf]], np.float32)
    ei = np.array([[9, -1, -1]], np.int64)
    ed = np.array([[2.0, np.inf, np.inf]], np.float32)
    ids, ds_ = compose_topk(bi, bd, ei, ed, 3)
    assert ids.tolist() == [[5, 9, 7]]
    np.testing.assert_array_equal(ds_, [[1.0, 2.0, 3.0]])
    # ties prefer the base side (stable merge keeps static results stable)
    ids, _ = compose_topk(np.array([[5]], np.int64),
                          np.array([[2.0]], np.float32),
                          np.array([[9]], np.int64),
                          np.array([[2.0]], np.float32), 1)
    assert ids.tolist() == [[5]]


# ---------------------------------------------------------------------------
# empty-delta bit-parity on all three backend layers
# ---------------------------------------------------------------------------
def _parity_queries(ds, b=6, seed=31):
    vecs, _, schema = ds
    rng = np.random.default_rng(seed)
    qs = rng.normal(size=(b, vecs.shape[1])).astype(np.float32)
    return qs, F.Equality("i0", 3)


def _assert_bit_identical(r0, r1):
    np.testing.assert_array_equal(r0.ids, r1.ids)
    np.testing.assert_array_equal(r0.dists, r1.dists)
    np.testing.assert_array_equal(r0.routed_brute, r1.routed_brute)


def test_empty_delta_bit_parity_local(ds):
    be = _fresh_local(ds)
    qs, flt = _parity_queries(ds)
    for force in (None, "graph", "brute"):
        opts = OPTS.with_(force=force)
        before = router.execute(be, qs, flt, opts)
        # activate the live path without mutating anything observable
        assert be.delete([10 ** 9]) == 0
        assert be.live_view() is not None
        after = router.execute(be, qs, flt, opts)
        _assert_bit_identical(before, after)


def test_empty_delta_bit_parity_sharded(ds):
    vecs, attrs, _ = ds
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    be = ShardedBackend.build(vecs, attrs, mesh,
                              BuildSpec(hnsw=HnswParams(M=8, efc=48, seed=3)))
    qs, flt = _parity_queries(ds)
    for force in (None, "graph", "brute"):
        opts = OPTS.with_(force=force)
        before = router.execute(be, qs, flt, opts)
        assert be.delete([10 ** 9]) == 0
        after = router.execute(be, qs, flt, opts)
        _assert_bit_identical(before, after)


def test_empty_delta_bit_parity_caching(ds):
    be = CachingBackend(_fresh_local(ds), CacheSpec())
    qs, flt = _parity_queries(ds)
    before = router.execute(be, qs, flt, OPTS)
    assert be.delete([10 ** 9]) == 0
    after = router.execute(be, qs, flt, OPTS)
    _assert_bit_identical(before, after)


# ---------------------------------------------------------------------------
# upsert is found, delete is gone -- on every route
# ---------------------------------------------------------------------------
def test_upsert_found_delete_gone_all_routes(ds):
    vecs, attrs, schema = ds
    be = _fresh_local(ds)
    rng = np.random.default_rng(41)
    q = rng.normal(size=(1, vecs.shape[1])).astype(np.float32)
    ints, floats = _matching_attrs(attrs, schema)
    nid = int(be.upsert(q + 1e-3, ints, floats)[0])
    assert nid == vecs.shape[0]                 # positional id allocation
    flt = F.Equality("i0", 3)
    for force in (None, "graph", "brute"):
        r = router.execute(be, q, flt, OPTS.with_(force=force))
        assert r.ids[0, 0] == nid, force        # nearest by construction
    assert be.delete([nid]) == 1
    for force in (None, "graph", "brute"):
        r = router.execute(be, q, flt, OPTS.with_(force=force))
        assert nid not in r.ids, force
    # replace= retires the old id and issues a fresh handle
    rid = int(be.upsert(q + 2e-3, ints, floats)[0])
    rid2 = int(be.upsert(q + 3e-3, ints, floats, replace=[rid])[0])
    assert rid2 != rid
    r = router.execute(be, q, flt, OPTS.with_(force="brute"))
    assert rid2 in r.ids and rid not in r.ids


def test_base_delete_gone_on_graph_route(ds):
    vecs, attrs, schema = ds
    be = _fresh_local(ds)
    flt = F.Equality("i0", 3)
    rng = np.random.default_rng(43)
    q = rng.normal(size=(1, vecs.shape[1])).astype(np.float32)
    r0 = router.execute(be, q, flt, OPTS.with_(force="graph"))
    victim = int(r0.ids[0, 0])
    assert be.delete([victim]) == 1
    r1 = router.execute(be, q, flt, OPTS.with_(force="graph"))
    assert victim not in r1.ids
    r2 = router.execute(be, q, flt, OPTS.with_(force="brute"))
    assert victim not in r2.ids


def test_sharded_upsert_delete_merge(ds):
    vecs, attrs, schema = ds
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    be = ShardedBackend.build(vecs, attrs, mesh,
                              BuildSpec(hnsw=HnswParams(M=8, efc=48, seed=3)))
    rng = np.random.default_rng(47)
    q = rng.normal(size=(1, vecs.shape[1])).astype(np.float32)
    ints, floats = _matching_attrs(attrs, schema, count=3)
    ids = be.upsert(np.concatenate([q + 1e-3, q + 2e-3, q + 3e-3]),
                    ints, floats)
    flt = F.Equality("i0", 3)
    for force in ("graph", "brute"):
        r = router.execute(be, q, flt, OPTS.with_(force=force))
        assert int(r.ids[0, 0]) == int(ids[0]), force
    assert be.delete([int(ids[0])]) == 1
    for force in ("graph", "brute"):
        r = router.execute(be, q, flt, OPTS.with_(force=force))
        assert int(ids[0]) not in r.ids, force
        assert int(r.ids[0, 0]) == int(ids[1]), force
    out = be.merge(wave=256)
    assert out["merged_slots"] == 3
    assert be.live_stats()["delta_rows"] == 0
    for force in ("graph", "brute"):
        r = router.execute(be, q, flt, OPTS.with_(force=force))
        assert int(r.ids[0, 0]) == int(ids[1]), force
        assert int(ids[0]) not in r.ids, force


# ---------------------------------------------------------------------------
# caches: deleted ids never served, scoped invalidation
# ---------------------------------------------------------------------------
def test_delete_not_served_from_warm_caches(ds):
    vecs, _, _ = ds
    cb = CachingBackend(_fresh_local(ds),
                        CacheSpec(candidate_p_max=0.5))
    rng = np.random.default_rng(53)
    flt = F.Equality("i0", 3)
    opts = OPTS.with_(force="brute")
    # two distinct batches (semantic can't serve them) admit the signature
    # into the candidate cache on its second brute miss...
    for _ in range(2):
        router.execute(cb, rng.normal(size=(4, vecs.shape[1]))
                       .astype(np.float32), flt, opts)
    qs = rng.normal(size=(4, vecs.shape[1])).astype(np.float32)
    cb_r = router.execute(cb, qs, flt, opts)     # ...3rd: candidate hit
    router.execute(cb, qs, flt, opts)            # exact repeat: semantic hit
    st = cb.cache_stats()
    assert st["candidates"]["size"] > 0 and st["candidates"]["hits"] > 0
    assert st["semantic"]["size"] > 0 and st["semantic"]["hits"] > 0
    victim = int(cb_r.ids[0, 0])
    assert cb.delete([victim]) == 1
    r1 = router.execute(cb, qs, flt, opts)
    assert victim not in r1.ids
    # exactness: composed warm-cache results == a fresh uncached backend
    fresh = router.execute(LocalBackend(cb.inner.index), qs, flt, opts)
    np.testing.assert_array_equal(r1.ids, fresh.ids)
    np.testing.assert_allclose(r1.dists, fresh.dists, rtol=1e-5, atol=1e-6)
    # ...and those hits really were served from the warm block
    assert cb.cache_stats()["candidates"]["composed"] > 0


def test_vector_only_upsert_keeps_selectivity_and_candidates_warm(ds):
    vecs, attrs, schema = ds
    cb = CachingBackend(_fresh_local(ds),
                        CacheSpec(candidate_p_max=0.5, semantic=False))
    rng = np.random.default_rng(59)
    qs = rng.normal(size=(4, vecs.shape[1])).astype(np.float32)
    flt = F.Equality("i0", 3)
    opts = OPTS.with_(force="brute")
    for _ in range(3):
        router.execute(cb, qs, flt, opts)
    st0 = cb.cache_stats()
    assert st0["selectivity"]["size"] > 0 and st0["candidates"]["size"] > 0
    ints, floats = _matching_attrs(attrs, schema)
    cb.upsert(qs[:1] + 1e-3, ints, floats)  # vector-only mutation
    router.execute(cb, qs, flt, opts)
    st1 = cb.cache_stats()
    # both layers survived the bump: no new misses, entries intact
    assert st1["selectivity"]["size"] == st0["selectivity"]["size"]
    assert st1["selectivity"]["misses"] == st0["selectivity"]["misses"]
    assert st1["candidates"]["size"] == st0["candidates"]["size"]
    assert st1["candidates"]["misses"] == st0["candidates"]["misses"]
    assert cb.invalidations == 1            # scoped, not a full clear


def test_scoped_epochs_matrix(ds):
    be = _fresh_local(ds)
    fi = be.index
    v0 = fi.versions()
    assert v0 == {"vectors": 0, "attributes": 0, "graph": 0}
    vecs, attrs, schema = ds
    ints, floats = _matching_attrs(attrs, schema)
    fi.upsert(np.zeros((1, vecs.shape[1]), np.float32), ints, floats)
    assert fi.versions() == {"vectors": 1, "attributes": 0, "graph": 0}
    fi.delete([10 ** 9])                    # found nothing: no bump
    assert fi.versions()["vectors"] == 1
    fi.merge(wave=256)
    # local merge: sample untouched -> attributes epoch must NOT move
    assert fi.versions() == {"vectors": 2, "attributes": 0, "graph": 1}


# ---------------------------------------------------------------------------
# graph_arrays memoization x mutation: no full re-upload
# ---------------------------------------------------------------------------
def test_no_graph_reupload_on_delete_only_mutation(ds):
    be = _fresh_local(ds)
    fi = be.index
    g_vec, g_nb = fi.g["vectors"], fi.g["neighbors0"]
    g_ai = fi.g["attrs_int"]
    assert fi.delete([0]) == 1
    # tombstones overlay an alive mask; the uploaded arrays stay put
    assert fi.g["vectors"] is g_vec
    assert fi.g["neighbors0"] is g_nb
    assert fi.g["attrs_int"] is g_ai
    assert "alive" in fi.g and not bool(fi.g["alive"][0])
    # component-scoped refresh re-uploads only what moved
    fi.bump_version(components=("attributes",))
    assert fi.g["vectors"] is g_vec          # untouched component reused
    assert fi.g["neighbors0"] is g_nb
    # legacy full bump still re-uploads everything
    fi.bump_version()
    assert fi.g["vectors"] is not g_vec


# ---------------------------------------------------------------------------
# merge equivalence
# ---------------------------------------------------------------------------
def test_merge_folds_to_equivalent_static_index(ds):
    vecs, attrs, schema = ds
    be = _fresh_local(ds)
    rng = np.random.default_rng(61)
    extra = rng.normal(size=(40, vecs.shape[1])).astype(np.float32)
    ints, floats = _matching_attrs(attrs, schema, count=40)
    ids = be.upsert(extra, ints, floats)
    dead_base = [int(np.nonzero(
        attrs.ints[:, schema.int_index("i0")] == 3)[0][0])]
    dead_delta = [int(ids[5])]
    assert be.delete(dead_base + dead_delta) == 2
    out = be.merge(wave=256)
    assert out["merged_slots"] == 40 and out["n"] == vecs.shape[0] + 40
    st = be.live_stats()
    assert st["delta_rows"] == 0 and st["dead_base_rows"] == 2
    # ground truth: exact top-k over live matching rows of the merged corpus
    all_vecs = np.concatenate([vecs, extra])
    col = schema.int_index("i0")
    all_i0 = np.concatenate([attrs.ints[:, col], ints[:, col]])
    alive = np.ones((len(all_vecs),), bool)
    alive[dead_base + dead_delta] = False
    rows = np.nonzero((all_i0 == 3) & alive)[0]
    qs = rng.normal(size=(5, vecs.shape[1])).astype(np.float32)
    want_ids, want_d = _exact_topk(all_vecs, qs, rows, OPTS.k)
    got = router.execute(be, qs, F.Equality("i0", 3),
                         OPTS.with_(force="brute"))
    np.testing.assert_array_equal(got.ids, want_ids)
    np.testing.assert_allclose(got.dists, want_d, rtol=1e-5, atol=1e-5)
    # graph route over the bulk-built merged graph serves the same ids
    # near the top (recall, not bit-parity: the graphs legitimately differ)
    gg = router.execute(be, qs, F.Equality("i0", 3),
                        OPTS.with_(force="graph"))
    overlap = np.mean([
        len(set(gg.ids[i][gg.ids[i] >= 0]) & set(want_ids[i])) / OPTS.k
        for i in range(len(qs))])
    assert overlap >= 0.9
    assert int(ids[5]) not in got.ids           # dead delta id stays dead
    assert int(ids[5]) not in gg.ids


# ---------------------------------------------------------------------------
# edge cases
# ---------------------------------------------------------------------------
def test_empty_index_then_delta_only_parity():
    rng = np.random.default_rng(67)
    d = 16
    schema = paper_schema()
    attrs0 = random_attributes(schema, 0, seed=1)
    be = LocalBackend(FavorIndex.build(
        np.zeros((0, d), np.float32), attrs0, HnswParams(M=8, efc=48,
                                                         seed=3)))
    qs = rng.normal(size=(3, d)).astype(np.float32)
    flt = F.Equality("i0", 3)
    r = router.execute(be, qs, flt, OPTS)
    assert (r.ids == -1).all() and np.isinf(r.dists).all()
    # stream in a corpus; ids are 0..n-1 (positional over an empty base)
    n = 64
    vecs = rng.normal(size=(n, d)).astype(np.float32)
    attrs = random_attributes(schema, n, seed=5)
    ids = be.upsert(vecs, attrs.ints, attrs.floats)
    assert ids.tolist() == list(range(n))
    got = router.execute(be, qs, flt, OPTS.with_(force="brute"))
    # parity vs a from-scratch static build over the same rows
    want = router.execute(
        LocalBackend(FavorIndex.build(vecs, attrs,
                                      HnswParams(M=8, efc=48, seed=3))),
        qs, flt, OPTS.with_(force="brute"))
    np.testing.assert_array_equal(got.ids, want.ids)
    np.testing.assert_allclose(got.dists, want.dists, rtol=1e-6, atol=1e-6)


def test_single_element_index_mutation():
    rng = np.random.default_rng(71)
    d = 16
    schema = paper_schema()
    attrs = random_attributes(schema, 1, seed=2)
    be = LocalBackend(FavorIndex.build(
        rng.normal(size=(1, d)).astype(np.float32), attrs,
        HnswParams(M=8, efc=48, seed=3)))
    q = rng.normal(size=(1, d)).astype(np.float32)
    flt = F.TrueFilter()
    r = router.execute(be, q, flt, OPTS)
    assert r.ids[0, 0] == 0
    assert be.delete([0]) == 1
    r = router.execute(be, q, flt, OPTS)
    assert (r.ids == -1).all()


def test_delete_everything_then_search(ds):
    vecs, _, _ = ds
    be = _fresh_local(ds)
    assert be.delete(list(range(vecs.shape[0]))) == vecs.shape[0]
    rng = np.random.default_rng(73)
    qs = rng.normal(size=(3, vecs.shape[1])).astype(np.float32)
    for force in ("graph", "brute"):
        r = router.execute(be, qs, F.TrueFilter(), OPTS.with_(force=force))
        assert (r.ids == -1).all(), force    # no ids, not garbage
        assert np.isinf(r.dists).all(), force


def test_insert_after_finalize_bulk_add(ds):
    vecs, attrs, _ = ds
    fi = FavorIndex.build(vecs[:256], random_attributes(paper_schema(), 256,
                                                        seed=13),
                          HnswParams(M=8, efc=48, seed=3))
    grown = build_hnsw_bulk(vecs[:256], HnswParams(M=8, efc=48, seed=3))
    assert grown.n == 256
    from repro.index.bulk import bulk_add
    grown2 = bulk_add(grown, vecs[256:384], wave=64)
    assert grown2.n == 384
    # every appended row is reachable and nearest-to-itself
    from repro.core.search import graph_arrays, favor_graph_search
    from repro.core.search import SearchConfig
    g = graph_arrays(grown2, random_attributes(paper_schema(), 384, seed=13),
                     version=0)
    import jax.numpy as jnp
    qs = vecs[256:264]
    progs = {
        "valid": jnp.ones((8, 1), jnp.float32),
        "imask": jnp.full((8, 1, 2), np.uint32(0xFFFFFFFF), jnp.uint32),
        "flo": jnp.full((8, 1, 1), -np.inf, jnp.float32),
        "fhi": jnp.full((8, 1, 1), np.inf, jnp.float32),
    }
    out = favor_graph_search(g, jnp.asarray(qs), progs,
                             jnp.zeros((8,), jnp.float32),
                             SearchConfig(k=1, ef=64, pbar_min=0.0))
    np.testing.assert_array_equal(np.asarray(out["ids"])[:, 0],
                                  np.arange(256, 264))


# ---------------------------------------------------------------------------
# bulk build recall
# ---------------------------------------------------------------------------
def test_bulk_build_recall_matches_sequential(ds):
    vecs, attrs, _ = ds
    n = 512
    params = HnswParams(M=8, efc=48, seed=3)
    seq = FavorIndex.build(vecs[:n], random_attributes(paper_schema(), n,
                                                       seed=13), params)
    blk = FavorIndex(build_hnsw_bulk(vecs[:n], params, wave=128),
                     random_attributes(paper_schema(), n, seed=13))
    rng = np.random.default_rng(79)
    qs = rng.normal(size=(32, vecs.shape[1])).astype(np.float32)
    want, _ = _exact_topk(vecs[:n], qs, np.arange(n), 10)
    rec = {}
    for name, fi in (("seq", seq), ("bulk", blk)):
        r = router.execute(LocalBackend(fi), qs, F.TrueFilter(),
                           OPTS.with_(force="graph"))
        rec[name] = np.mean([
            len(set(r.ids[i]) & set(want[i])) / 10 for i in range(len(qs))])
    assert rec["bulk"] >= rec["seq"] - 0.05, rec
    assert rec["bulk"] >= 0.8, rec


# ---------------------------------------------------------------------------
# quantization persistence
# ---------------------------------------------------------------------------
def test_quant_state_roundtrip(tmp_path, ds):
    vecs, attrs, _ = ds
    spec = BuildSpec(hnsw=HnswParams(M=8, efc=48, seed=3),
                     quant=QuantSpec(m=8, nbits=5, train_iters=10, rerank=4))
    fi = FavorIndex.build(vecs, attrs, spec=spec)
    rng = np.random.default_rng(83)
    qs = rng.normal(size=(4, vecs.shape[1])).astype(np.float32)
    opts = OPTS.with_(force="brute", use_pq=True)
    flt = F.Equality("i0", 3)
    want = router.execute(LocalBackend(fi), qs, flt, opts)
    path = str(tmp_path / "idx")
    fi.save(path)
    # the reloaded index serves use_pq with the PERSISTED codes -- results
    # are bit-identical, proving no re-train/re-encode happened
    re = FavorIndex.load(path, spec=spec)
    assert re.codebook is not None
    n = fi.index.n
    np.testing.assert_array_equal(np.asarray(re._codes)[:n],
                                  np.asarray(fi._codes)[:n])
    got = router.execute(LocalBackend(re), qs, flt, opts)
    np.testing.assert_array_equal(want.ids, got.ids)
    np.testing.assert_allclose(want.dists, got.dists, rtol=1e-5, atol=1e-6)
    # graph_quant route works from persisted state too
    gq = OPTS.with_(force="graph", graph_quant="pq")
    r1 = router.execute(LocalBackend(fi), qs, flt, gq)
    r2 = router.execute(LocalBackend(re), qs, flt, gq)
    np.testing.assert_array_equal(r1.ids, r2.ids)


def test_quant_requested_but_absent_raises(tmp_path, ds):
    vecs, attrs, _ = ds
    fi = FavorIndex.build(vecs[:128],
                          random_attributes(paper_schema(), 128, seed=13),
                          HnswParams(M=8, efc=48, seed=3))
    path = str(tmp_path / "plain")
    fi.save(path)
    with pytest.raises(ValueError, match="without quantization state"):
        FavorIndex.load(path, spec=BuildSpec(quant=QuantSpec(m=8, nbits=5)))


def test_save_warns_on_unmerged_mutations(tmp_path, ds):
    vecs, attrs, schema = ds
    fi = FavorIndex.build(vecs[:128],
                          random_attributes(paper_schema(), 128, seed=13),
                          HnswParams(M=8, efc=48, seed=3))
    ints, floats = _matching_attrs(attrs, schema)
    fi.upsert(np.zeros((1, vecs.shape[1]), np.float32), ints, floats)
    with pytest.warns(UserWarning, match="unmerged live mutations"):
        fi.save(str(tmp_path / "dirty"))


# ---------------------------------------------------------------------------
# ServeEngine mutation API + merge scheduling
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("merge_delta_frac", [0.01, None])
def test_engine_mutation_stats_and_auto_merge(ds, merge_delta_frac):
    """With ``merge_delta_frac`` None nothing merges: the live delta holds
    every upserted row less the deleted one, and serves them."""
    vecs, attrs, schema = ds
    eng = ServeEngine(_fresh_local(ds), SearchOptions(k=5, ef=48),
                      merge_delta_frac=merge_delta_frac)
    n = vecs.shape[0]
    rng = np.random.default_rng(89)
    ints, floats = _matching_attrs(attrs, schema, count=9)
    ids = eng.upsert(rng.normal(size=(9, vecs.shape[1])).astype(np.float32),
                     ints, floats)
    assert eng.delete([int(ids[0])]) == 1
    flt = F.Equality("i0", 3)
    for _ in range(3):
        eng.submit(rng.normal(size=(vecs.shape[1],)).astype(np.float32), flt)
    out = eng.run()
    assert len(out) == 3
    st = eng.stats["mutations"]
    assert st["upserts"] == 9 and st["deletes"] == 1
    if merge_delta_frac is None:
        assert st["auto_merges"] == 0
        assert st["delta_rows"] == 8 and st["delta_slots"] == 9
        assert st["base_rows"] == n
        q = eng.backend.index.live.delta.vectors[1].astype(np.float32)
        eng.submit(q, flt)
        assert int(ids[1]) in eng.run()[0].ids   # served from the delta
        return
    assert st["auto_merges"] == 1           # 9/768 > 1% -> merged post-step
    assert st["delta_rows"] == 0 and st["base_rows"] == n + 9
    # post-merge serving still finds a surviving upserted row
    q = np.asarray(eng.backend.index.index.vectors[int(ids[1])], np.float32)
    eng.submit(q, flt)
    r = eng.run()[0]
    assert int(ids[1]) in r.ids


def test_engine_mutation_unsupported_backend_raises(ds):
    eng = ServeEngine(_fresh_local(ds), SearchOptions(k=5, ef=48))

    class Static:
        def validate(self, o):
            pass

        def version(self):
            return 0

    eng.backend = Static()
    with pytest.raises(ValueError, match="does not support live mutation"):
        eng.upsert(np.zeros((1, 16), np.float32))
    with pytest.raises(ValueError, match="merge_delta_frac"):
        ServeEngine(_fresh_local(ds), SearchOptions(k=5, ef=48),
                    merge_delta_frac=0.0)
