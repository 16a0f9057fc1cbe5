"""Shape-stable execution: BatchSpec policy, pad/unpad round-trips, the
compiled-shape registry + warmup, and the acceptance bar -- bucket-padded
execution is bit-identical to the unpadded path on local, sharded and
caching backends, including the all-graph / all-brute / empty-sub-batch
edges -- with a hypothesis sweep over batch sizes and filter mixes."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (BatchSpec, BuildSpec, FavorIndex, HnswParams,
                        LocalBackend, QuantSpec, SearchOptions,
                        ShardedBackend, paper_filters, router)
from repro.core import filters as F
from repro.core.batching import (ShapeRegistry, pad_programs, pad_to_bucket,
                                 unpad, warmup)
from repro.serving import ServeEngine

SPEC = BatchSpec(min_bucket=4, max_bucket=32)
OPTS = SearchOptions(k=10, ef=64)
OPTS_B = OPTS.with_(batch=SPEC)


# ---------------------------------------------------------------------------
# BatchSpec policy
# ---------------------------------------------------------------------------
def test_batchspec_validation():
    with pytest.raises(ValueError, match="power of two"):
        BatchSpec(min_bucket=6)
    with pytest.raises(ValueError, match="power of two"):
        BatchSpec(max_bucket=100)
    with pytest.raises(ValueError, match="min_bucket"):
        BatchSpec(min_bucket=64, max_bucket=32)
    with pytest.raises(ValueError, match="pad_policy"):
        BatchSpec(pad_policy="wrap")
    with pytest.raises(TypeError, match="BatchSpec"):
        SearchOptions(batch={"min_bucket": 8})


def test_bucket_ladder_and_lookup():
    assert SPEC.buckets() == (4, 8, 16, 32)
    assert [SPEC.bucket_for(n) for n in (1, 4, 5, 8, 9, 32)] == \
        [4, 4, 8, 8, 16, 32]
    # above max_bucket: round up to a multiple of it
    assert SPEC.bucket_for(33) == 64
    assert SPEC.bucket_for(65) == 96
    with pytest.raises(ValueError, match="n >= 1"):
        SPEC.bucket_for(0)


def _stacked(schema, flts):
    return {k: jnp.asarray(v) for k, v in F.stack_programs(
        [F.compile_filter(f, schema) for f in flts]).items()}


def test_pad_rows_match_nothing_and_unpad_roundtrip(small_dataset):
    _, attrs, schema = small_dataset
    flts = [paper_filters(schema)["range_50"], F.TrueFilter(),
            paper_filters(schema)["logic"]]
    progs = _stacked(schema, flts)
    queries = jnp.asarray(np.random.default_rng(0).normal(
        size=(3, 16)).astype(np.float32))
    qp, pp, ph, valid = pad_to_bucket(SPEC, queries, progs,
                                      np.ones((3,), np.float32))
    assert qp.shape[0] == 4 and valid.tolist() == [True] * 3 + [False]
    assert ph.shape == (4,) and ph[3] == 0.0
    # pad program rows are always-false: they match no attribute row
    mask = np.asarray(F.eval_program_batched(
        {k: np.asarray(v) for k, v in pp.items()}, attrs.ints, attrs.floats))
    assert not mask[3].any() and mask[1].all()  # TrueFilter row untouched
    # unpad returns the original rows bit-identically
    uq, up = unpad(3, np.asarray(qp), np.asarray(ph))
    np.testing.assert_array_equal(uq, np.asarray(queries))
    for k in progs:
        np.testing.assert_array_equal(np.asarray(pp[k])[:3],
                                      np.asarray(progs[k]))
    # exact bucket size: nothing padded, same objects pass through
    q4 = jnp.concatenate([queries, queries[:1]])
    qp4, pp4, _, v4 = pad_to_bucket(SPEC, q4, progs)
    assert qp4 is q4 and pp4 is progs and v4.all()
    pp_only, v = pad_programs(SPEC, progs)
    assert np.asarray(pp_only["valid"]).shape[0] == 4 and not v[3]


def test_shape_registry_accounting():
    reg = ShapeRegistry()
    assert reg.record("graph", 8, 5, OPTS) is True    # compile
    assert reg.record("graph", 8, 7, OPTS) is False   # reuse
    assert reg.record("graph", 16, 9, OPTS) is True
    assert reg.record("brute", 8, 8, OPTS) is True
    # a different static config is a different executable
    assert reg.record("graph", 8, 8, OPTS.with_(ef=48)) is True
    st = reg.stats()
    assert st["compiled_shapes"] == 4 and st["compile_events"] == 4
    assert st["calls"] == 5
    assert st["pad_rows"] == 3 + 1 + 7 and st["real_rows"] == 5 + 7 + 9 + 8 + 8
    assert reg.sizes_by_kind() == {"graph": (8, 16), "brute": (8,)}
    reg.reset_rows()
    st = reg.stats()
    assert st["pad_rows"] == 0 and st["compiled_shapes"] == 4


def test_gather_distance_valid_mask(small_index, small_dataset):
    """Kernel-op mask contract on the graph-expansion op: masked rows go
    all-+inf / no-TD, unmasked rows are untouched bit-for-bit."""
    from repro.kernels.gather_distance import ops as gd_ops
    vecs, _, schema = small_dataset
    g = small_index.g
    rng = np.random.default_rng(3)
    b, m = 4, 8
    queries = jnp.asarray(rng.normal(size=(b, vecs.shape[1]))
                          .astype(np.float32))
    nbr_ids = jnp.asarray(rng.integers(-1, vecs.shape[0], size=(b, m),
                                       dtype=np.int32))
    progs = _stacked(schema, [paper_filters(schema)["range_50"]] * b)
    dvec = jnp.zeros((b,), jnp.float32)
    args = (g["vectors"], g["norms"], g["attrs_int"], g["attrs_float"],
            queries, nbr_ids, progs, dvec)
    d0, td0 = gd_ops.gather_distance(*args)
    valid = np.array([True, False, True, False])
    d1, td1 = gd_ops.gather_distance(*args, valid=valid)
    np.testing.assert_array_equal(np.asarray(d0)[[0, 2]],
                                  np.asarray(d1)[[0, 2]])
    np.testing.assert_array_equal(np.asarray(td0)[[0, 2]],
                                  np.asarray(td1)[[0, 2]])
    assert np.isinf(np.asarray(d1)[[1, 3]]).all()
    assert not np.asarray(td1)[[1, 3]].any()


def test_take_programs_stays_on_device(small_dataset):
    _, _, schema = small_dataset
    progs = _stacked(schema, [F.TrueFilter()] * 5)
    sub = router.take_programs(progs, np.array([4, 1, 2]))
    for k in progs:
        assert isinstance(sub[k], jax.Array)
        np.testing.assert_array_equal(np.asarray(sub[k]),
                                      np.asarray(progs[k])[[4, 1, 2]])


# ---------------------------------------------------------------------------
# Bit-identical parity: bucket-padded vs. disabled
# ---------------------------------------------------------------------------
def _filter_pool(schema):
    pf = paper_filters(schema)
    return [pf["equality_bool"], pf["equality_int"], pf["range_10"],
            pf["logic"], F.TrueFilter(), F.FalseFilter(),
            F.And(F.Equality("i0", 3), F.Range("f0", 11.0, 13.0))]


def _workload(schema, dim, n, seed):
    rng = np.random.default_rng(seed)
    pool = _filter_pool(schema)
    qs = rng.normal(size=(n, dim)).astype(np.float32)
    flts = [pool[i] for i in rng.integers(0, len(pool), n)]
    return qs, flts


def _assert_bit_identical(ra, rb):
    np.testing.assert_array_equal(ra.ids, rb.ids)
    np.testing.assert_array_equal(ra.dists, rb.dists)
    np.testing.assert_array_equal(ra.p_hat, rb.p_hat)
    np.testing.assert_array_equal(ra.routed_brute, rb.routed_brute)
    if ra.hops is None:
        assert rb.hops is None and rb.path_td is None
    else:
        np.testing.assert_array_equal(ra.hops, rb.hops)
        np.testing.assert_array_equal(ra.path_td, rb.path_td)


@pytest.fixture(scope="module")
def quant_local(small_index, small_dataset):
    vecs, attrs, _ = small_dataset
    return LocalBackend(FavorIndex(
        small_index.index, attrs,
        BuildSpec(quant=QuantSpec(m=8, nbits=5, train_iters=8, rerank=4))))


@pytest.fixture(scope="module")
def sharded_1dev(small_dataset):
    vecs, attrs, _ = small_dataset
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    return ShardedBackend.build(vecs, attrs, mesh,
                                BuildSpec(hnsw=HnswParams(M=8, efc=48,
                                                          seed=3)))


@pytest.mark.parametrize("force,n", [
    (None, 7),      # mixed routes, odd size
    (None, 4),      # exact bucket (no padding)
    ("graph", 5),   # all-graph: empty brute sub-batch
    ("brute", 3),   # all-brute: empty graph sub-batch
    (None, 1),      # singleton batch
])
def test_local_padded_parity(small_index, small_dataset, force, n):
    vecs, _, schema = small_dataset
    qs, flts = _workload(schema, vecs.shape[1], n, seed=50 + n)
    backend = LocalBackend(small_index)
    ra = router.execute(backend, qs, flts, OPTS.with_(force=force))
    rb = router.execute(backend, qs, flts, OPTS_B.with_(force=force))
    _assert_bit_identical(ra, rb)
    assert ra.hops is not None  # LocalBackend reports traversal diagnostics


@pytest.mark.parametrize("n", [6, 1])
def test_local_padded_parity_pq(quant_local, small_dataset, n):
    vecs, _, schema = small_dataset
    qs, flts = _workload(schema, vecs.shape[1], n, seed=77)
    ra = router.execute(quant_local, qs, flts,
                        OPTS.with_(use_pq=True, force="brute"))
    rb = router.execute(quant_local, qs, flts,
                        OPTS_B.with_(use_pq=True, force="brute"))
    _assert_bit_identical(ra, rb)


def test_sharded_padded_parity_and_diag(sharded_1dev, small_dataset):
    vecs, _, schema = small_dataset
    for force, n in ((None, 7), ("brute", 3), ("graph", 5)):
        qs, flts = _workload(schema, vecs.shape[1], n, seed=60 + n)
        ra = router.execute(sharded_1dev, qs, flts, OPTS.with_(force=force))
        rb = router.execute(sharded_1dev, qs, flts, OPTS_B.with_(force=force))
        _assert_bit_identical(ra, rb)
    # the sharded top-k merge drops hops/path_td: None, not silently 0
    assert ra.hops is None and ra.path_td is None


def test_sharded_use_pallas_brute(sharded_1dev, small_dataset):
    """use_pallas now runs inside the shard_map path (was a ValueError)."""
    vecs, _, schema = small_dataset
    qs, flts = _workload(schema, vecs.shape[1], 5, seed=91)
    base = OPTS.with_(force="brute")
    rn = router.execute(sharded_1dev, qs, flts, base)
    rp = router.execute(sharded_1dev, qs, flts, base.with_(use_pallas=True))
    # kernel and jnp scan reduce in different orders: ids may swap on exact
    # distance ties, so compare per-row sets + distances (same bar as the
    # kernel suite)
    for i in range(len(qs)):
        assert set(rn.ids[i]) == set(rp.ids[i]), i
    np.testing.assert_allclose(rn.dists, rp.dists, rtol=1e-5, atol=1e-5)
    # and bucket padding composes with the kernel path bit-identically
    rpb = router.execute(sharded_1dev, qs, flts,
                         OPTS_B.with_(force="brute", use_pallas=True))
    _assert_bit_identical(rp, rpb)


def test_caching_padded_parity(small_index, small_dataset):
    from repro.cache import CachingBackend
    from repro.core import CacheSpec
    vecs, _, schema = small_dataset
    qs, flts = _workload(schema, vecs.shape[1], 6, seed=83)
    streams = [(qs, flts)] * 3  # repeats: semantic/candidate layers go hot
    results = {}
    stats = {}
    for tag, opts in (("raw", OPTS), ("padded", OPTS_B)):
        cb = CachingBackend(LocalBackend(small_index), CacheSpec())
        results[tag] = [router.execute(cb, q, f, opts) for q, f in streams]
        stats[tag] = cb.cache_stats()
    for ra, rb in zip(results["raw"], results["padded"]):
        _assert_bit_identical(ra, rb)
    # pad rows must not pollute the cache layers: identical hit/miss
    # counters whether the batch was bucket-padded or not
    for layer in ("selectivity", "candidates", "semantic"):
        assert stats["padded"][layer]["hits"] == stats["raw"][layer]["hits"]
        assert (stats["padded"][layer]["misses"]
                == stats["raw"][layer]["misses"]), layer


# ---------------------------------------------------------------------------
# hypothesis sweep (CI; the container skips without hypothesis installed)
# ---------------------------------------------------------------------------
try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @settings(max_examples=12, deadline=None,
              suppress_health_check=[HealthCheck.function_scoped_fixture])
    @given(n=st.integers(min_value=1, max_value=9),
           force=st.sampled_from([None, "graph", "brute"]),
           seed=st.integers(min_value=0, max_value=2 ** 16))
    def test_padded_parity_property(small_index, small_dataset, n, force,
                                    seed):
        """Property form of the parity bar: any batch size / filter mix /
        route pin gives bit-identical results with bucket padding on."""
        vecs, _, schema = small_dataset
        qs, flts = _workload(schema, vecs.shape[1], n, seed=seed)
        backend = LocalBackend(small_index)
        ra = router.execute(backend, qs, flts, OPTS.with_(force=force))
        rb = router.execute(backend, qs, flts, OPTS_B.with_(force=force))
        _assert_bit_identical(ra, rb)


# ---------------------------------------------------------------------------
# warmup + engine accounting
# ---------------------------------------------------------------------------
def test_engine_warmup_bounds_compiled_shapes(small_index, small_dataset):
    vecs, _, schema = small_dataset
    eng = ServeEngine(LocalBackend(small_index), OPTS_B, max_batch=16)
    ladder = eng.warmup()
    assert ladder == SPEC.buckets()
    st0 = eng.stats["batching"]
    # estimate + the selector's exact check + graph + brute, one
    # executable per bucket
    assert st0["compiled_shapes"] == 4 * len(ladder)
    qs, flts = _workload(schema, vecs.shape[1], 29, seed=13)
    for q, f in zip(qs, flts):
        eng.submit(q, f)
    out = eng.run()
    assert len(out) == 29
    st1 = eng.stats["batching"]
    # live traffic hit only warmed shapes: zero new compile events
    assert st1["compiled_shapes"] == st0["compiled_shapes"]
    for kind, sizes in st1["sizes"].items():
        assert set(sizes) <= set(ladder), (kind, sizes)
    assert st1["pad_rows"] > 0 and 0.0 < st1["pad_overhead"] < 1.0
    # local backends report per-request traversal diagnostics as ints
    assert isinstance(eng.stats["hops"], int)
    assert isinstance(eng.stats["path_td"], int)
    eng.reset_stats()
    st2 = eng.stats["batching"]
    assert st2["pad_rows"] == 0  # rows reset; compiled-shape set survives
    assert st2["compiled_shapes"] == st1["compiled_shapes"]
    assert eng.stats["hops"] == 0


def test_engine_warmup_unwraps_cache_and_custom_buckets(small_index):
    from repro.cache import CachingBackend
    from repro.core import CacheSpec
    cb = CachingBackend(LocalBackend(small_index), CacheSpec())
    eng = ServeEngine(cb, OPTS_B, max_batch=16)
    assert eng.warmup(buckets=(4, 8)) == (4, 8)
    st = eng.stats["batching"]
    assert st["compiled_shapes"] == 4 * 2
    # warmup drove the inner backend: no cache-layer counter pollution
    cs = eng.stats["cache"]
    assert cs["semantic"]["misses"] == 0 and cs["selectivity"]["misses"] == 0


def test_warmup_requires_batch_and_honors_force(small_index):
    # batch=None traffic would never reuse warmed shapes: loud, not silent
    with pytest.raises(ValueError, match="batch"):
        ServeEngine(LocalBackend(small_index), OPTS).warmup()
    # a pinned route skips the other route's executables entirely
    eng = ServeEngine(LocalBackend(small_index), OPTS_B.with_(force="brute"))
    ladder = eng.warmup(buckets=(4, 8))
    assert eng.stats["batching"]["compiled_shapes"] == 2 * len(ladder)
    assert "graph" not in eng.stats["batching"]["sizes"]


def test_engine_sharded_hops_none_safe(sharded_1dev, small_dataset):
    vecs, _, schema = small_dataset
    eng = ServeEngine(sharded_1dev, OPTS_B.with_(force="graph"), max_batch=8)
    qs, flts = _workload(schema, vecs.shape[1], 5, seed=29)
    for q, f in zip(qs, flts):
        eng.submit(q, f)
    eng.run()
    assert eng.stats["hops"] is None and eng.stats["path_td"] is None
