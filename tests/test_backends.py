"""Unified search API: SearchOptions validation, router
routing parity between LocalBackend and ShardedBackend, ServeEngine over
both backends (the sharded 2-device run lives in a subprocess)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax

from repro.core import (BuildSpec, FavorIndex, HnswParams, LocalBackend,
                        QuantSpec, SearchOptions, ShardedBackend,
                        paper_filters, router)
from repro.core import filters as F
from repro.serving import ServeEngine


# ---------------------------------------------------------------------------
# options validation
# ---------------------------------------------------------------------------
def test_search_options_validation():
    with pytest.raises(ValueError, match="force"):
        SearchOptions(force="brutal")          # typo must not auto-route
    with pytest.raises(ValueError, match="k must"):
        SearchOptions(k=0)
    with pytest.raises(ValueError, match="rerank"):
        SearchOptions(rerank=-1)
    assert SearchOptions(rerank=0).rerank == 0  # explicit 0 is preserved
    cfg = SearchOptions(k=5, ef=48, gamma=1.5).search_config()
    assert cfg.k == 5 and cfg.ef == 48 and cfg.gamma == 1.5


def test_quant_spec_validation():
    with pytest.raises(ValueError, match="kind"):
        QuantSpec(kind="opq")
    with pytest.raises(ValueError, match="nbits"):
        QuantSpec(nbits=9)
    with pytest.raises(ValueError, match="prefbf_chunk"):
        BuildSpec(prefbf_chunk=0)


def test_constructors_take_only_typed_specs(small_index, small_dataset):
    """FavorIndex takes a BuildSpec and ServeEngine a SearchOptions; any
    other third or second positional is a TypeError, not a guess."""
    from repro.core.selector import SelectorConfig
    _, attrs, _ = small_dataset
    for bad in ({"quant": None}, SelectorConfig(lam=0.02)):
        with pytest.raises(TypeError, match="BuildSpec"):
            FavorIndex(small_index.index, attrs, bad)
    for bad in ({"k": 5}, 5):
        with pytest.raises(TypeError, match="SearchOptions"):
            ServeEngine(small_index, bad)
    eng = ServeEngine(small_index, SearchOptions(k=5, ef=48), max_batch=8)
    assert isinstance(eng.backend, LocalBackend)
    assert eng.opts == SearchOptions(k=5, ef=48)


def test_plan_routes_force_and_threshold():
    p = np.array([0.001, 0.5])
    plan = router.plan_routes(p, lam=0.01)
    assert plan.brute.tolist() == [True, False]
    assert router.plan_routes(p, 0.01, "brute").brute.all()
    assert not router.plan_routes(p, 0.01, "graph").brute.any()
    with pytest.raises(ValueError, match="force"):
        router.plan_routes(p, 0.01, "bruteforce")


def test_filter_count_mismatch_is_value_error(small_index, small_dataset):
    vecs, _, schema = small_dataset
    qs = np.zeros((4, vecs.shape[1]), np.float32)
    flt = paper_filters(schema)["equality_bool"]
    with pytest.raises(ValueError, match="one filter per query"):
        small_index.query(qs, [flt] * 3, SearchOptions(k=5, ef=48))


def test_loaded_codebook_round_trips_quant_spec(small_index, small_dataset,
                                                tmp_path):
    """fi.spec must describe the codebook actually attached (not defaults),
    so it can rebuild an equivalent backend elsewhere."""
    vecs, attrs, _ = small_dataset
    fi = FavorIndex(small_index.index, attrs,
                    BuildSpec(quant=QuantSpec(m=4, nbits=5, train_iters=5,
                                              rerank=2)))
    fi.save(str(tmp_path / "idx"))
    fi2 = FavorIndex.load(str(tmp_path / "idx"))
    assert fi2.spec.quant.kind == "pq"
    assert fi2.spec.quant.m == 4 and fi2.spec.quant.nbits == 5


def test_sharded_sample_bounds(small_dataset):
    """build_sharded honors SelectorConfig-style min/max sample bounds."""
    from repro.core import distributed as dist
    vecs, attrs, _ = small_dataset
    hi = dist.build_sharded(vecs, attrs, 2, HnswParams(M=8, efc=32),
                            min_sample=256)
    assert hi.sample_rows * 2 >= 256
    lo = dist.build_sharded(vecs, attrs, 2, HnswParams(M=8, efc=32),
                            sample_rate=0.5, max_sample=128)
    assert lo.sample_rows * 2 <= 128


def test_explicit_rerank_zero_honored(small_index, small_dataset):
    """Regression for the falsy-kwarg bug: rerank=0 must NOT fall back to
    the index default (4).  rerank=0 and rerank=1 both exact-re-rank exactly
    the top-k ADC candidates, so their results must coincide."""
    vecs, attrs, schema = small_dataset
    fi = FavorIndex(small_index.index, attrs,
                    BuildSpec(quant=QuantSpec(m=8, nbits=4, train_iters=8,
                                              rerank=4)))
    rng = np.random.default_rng(33)
    qs = rng.normal(size=(5, vecs.shape[1])).astype(np.float32)
    flt = paper_filters(schema)["range_50"]
    base = SearchOptions(k=10, force="brute", use_pq=True)
    r0 = fi.query(qs, flt, base.with_(rerank=0))
    r1 = fi.query(qs, flt, base.with_(rerank=1))
    np.testing.assert_array_equal(r0.ids, r1.ids)


# ---------------------------------------------------------------------------
# backend parity on a single device (mesh 1x1)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def backends_1dev(small_index, small_dataset):
    vecs, attrs, _ = small_dataset
    spec = BuildSpec(hnsw=HnswParams(M=8, efc=48, seed=3),
                     quant=QuantSpec(m=8, nbits=5, train_iters=10, rerank=4))
    local = LocalBackend(FavorIndex(small_index.index, attrs,
                                    BuildSpec(quant=spec.quant)))
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    shard = ShardedBackend.build(vecs, attrs, mesh, spec,
                                 codebook=local.index.codebook)
    return local, shard


def test_backend_route_parity_1dev(backends_1dev, small_dataset):
    vecs, attrs, schema = small_dataset
    local, shard = backends_1dev
    rng = np.random.default_rng(40)
    qs = rng.normal(size=(6, vecs.shape[1])).astype(np.float32)
    opts = SearchOptions(k=10, ef=64)
    for name, flt in paper_filters(schema).items():
        rl = router.execute(local, qs, flt, opts)
        rs = router.execute(shard, qs, flt, opts)
        # same selector, psum-combined estimate -> same routing decisions
        sel = float(F.eval_program(F.compile_filter(flt, schema), attrs.ints,
                                   attrs.floats).mean())
        if not 0.005 <= sel <= 0.02:  # skip the lambda boundary band
            np.testing.assert_array_equal(rl.routed_brute, rs.routed_brute,
                                          err_msg=name)
        # two independent 256-row samples: allow 3 sigma of estimator noise
        tol = 3.0 * np.sqrt(2.0 * sel * (1.0 - sel) / 256) + 0.01
        assert abs(rl.p_hat.mean() - rs.p_hat.mean()) < tol, name


def test_backend_brute_parity_1dev(backends_1dev, small_dataset):
    """Exact float32 brute scans must agree on global row ids; the sharded
    PQ brute (ADC scan + per-shard exact re-rank) must track the local PQ
    result within a small recall tolerance."""
    vecs, attrs, schema = small_dataset
    local, shard = backends_1dev
    rng = np.random.default_rng(41)
    qs = rng.normal(size=(5, vecs.shape[1])).astype(np.float32)
    flt = paper_filters(schema)["equality_int"]
    f32 = SearchOptions(k=10, ef=64, force="brute")
    rl = router.execute(local, qs, flt, f32)
    rs = router.execute(shard, qs, flt, f32)
    np.testing.assert_array_equal(rl.ids, rs.ids)

    pq = f32.with_(use_pq=True)
    rlq = router.execute(local, qs, flt, pq)
    rsq = router.execute(shard, qs, flt, pq)
    # same codebook, same rows -> overwhelmingly the same re-ranked ids
    agree = float((rlq.ids == rsq.ids).mean())
    assert agree > 0.9, agree
    assert shard.bytes_per_vector(quantized=True) == \
        local.index.bytes_per_vector(quantized=True)


def test_serve_engine_over_sharded_backend_1dev(backends_1dev, small_dataset):
    """The acceptance bar: ServeEngine runs unmodified over ShardedBackend."""
    vecs, _, schema = small_dataset
    _, shard = backends_1dev
    eng = ServeEngine(shard, SearchOptions(k=5, ef=48, use_pq=True),
                      max_batch=8)
    rng = np.random.default_rng(42)
    flts = list(paper_filters(schema).values())
    rids = [eng.submit(rng.normal(size=(vecs.shape[1],)).astype(np.float32),
                       flts[i % len(flts)]) for i in range(20)]
    out = eng.run()
    assert sorted(r.rid for r in out) == sorted(rids)
    assert eng.stats["graph"] + eng.stats["brute"] == 20


def test_sharded_use_pq_without_codebook_raises(small_dataset):
    vecs, attrs, _ = small_dataset
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    shard = ShardedBackend.build(vecs, attrs, mesh,
                                 BuildSpec(hnsw=HnswParams(M=8, efc=32)))
    with pytest.raises(ValueError, match="quantize"):
        router.execute(shard, np.zeros((2, vecs.shape[1]), np.float32),
                       F.TrueFilter(), SearchOptions(k=5, use_pq=True))


# ---------------------------------------------------------------------------
# 2-shard parity (subprocess: needs its own device count)
# ---------------------------------------------------------------------------
SCRIPT = textwrap.dedent("""
    import numpy as np, jax
    from repro.core import (BuildSpec, FavorIndex, HnswParams, LocalBackend,
                            QuantSpec, SearchOptions, ShardedBackend,
                            paper_filters, refimpl, router)
    from repro.core import filters as F
    from repro.serving import ServeEngine

    assert len(jax.devices()) == 2
    rng = np.random.default_rng(0)
    N, d = 2048, 16
    vecs = rng.normal(size=(N, d)).astype(np.float32)
    schema = F.paper_schema()
    attrs = F.random_attributes(schema, N, seed=1)
    spec = BuildSpec(hnsw=HnswParams(M=8, efc=40, seed=0),
                     quant=QuantSpec(m=8, nbits=6, train_iters=10, rerank=4))
    local = LocalBackend(FavorIndex.build(vecs, attrs, spec=spec))
    mesh = jax.make_mesh((1, 2), ("data", "model"))
    shard = ShardedBackend.build(vecs, attrs, mesh, spec,
                                 codebook=local.index.codebook)
    assert shard.sharded.n_shards == 2
    assert shard.sharded.arrays["codes"].shape == (N, 8)

    Q = 8
    qs = rng.normal(size=(Q, d)).astype(np.float32)
    opts = SearchOptions(k=10, ef=64)
    for name, flt in paper_filters(schema).items():
        mask = F.eval_program(F.compile_filter(flt, schema), attrs.ints,
                              attrs.floats)
        sel = float(mask.mean())
        rl = router.execute(local, qs, flt, opts)
        rs = router.execute(shard, qs, flt, opts)
        if not 0.005 <= sel <= 0.02:
            assert (rl.routed_brute == rs.routed_brute).all(), name
        truth = [refimpl.bruteforce_filtered(vecs, mask, q, 10)[0] for q in qs]
        rec_l = np.mean([refimpl.recall_at_k(rl.ids[i], truth[i], 10)
                         for i in range(Q)])
        rec_s = np.mean([refimpl.recall_at_k(rs.ids[i], truth[i], 10)
                         for i in range(Q)])
        assert rec_s >= rec_l - 0.1, (name, rec_l, rec_s)

    # exact f32 brute parity across the 2-shard merge
    flt = paper_filters(schema)["equality_int"]
    mask = F.eval_program(F.compile_filter(flt, schema), attrs.ints,
                          attrs.floats)
    f32 = SearchOptions(k=10, ef=64, force="brute")
    rl = router.execute(local, qs, flt, f32)
    rs = router.execute(shard, qs, flt, f32)
    assert (rl.ids == rs.ids).all()

    # sharded PQ brute: codes streamed per shard, exact re-rank -> recall
    # within 2pts of the f32 scan (same bar as the local quant tests)
    pq = f32.with_(use_pq=True)
    rsq = router.execute(shard, qs, flt, pq)
    truth = [refimpl.bruteforce_filtered(vecs, mask, q, 10)[0] for q in qs]
    rec_f32 = np.mean([refimpl.recall_at_k(rs.ids[i], truth[i], 10)
                       for i in range(Q)])
    rec_pq = np.mean([refimpl.recall_at_k(rsq.ids[i], truth[i], 10)
                      for i in range(Q)])
    assert rec_pq >= rec_f32 - 0.02, (rec_f32, rec_pq)

    # one unmodified ServeEngine over both backends
    for backend in (local, shard):
        eng = ServeEngine(backend, SearchOptions(k=10, ef=64, use_pq=True),
                          max_batch=8)
        for i in range(12):
            eng.submit(qs[i % Q], flt)
        out = eng.run()
        assert len(out) == 12
    print("BACKEND_PARITY_OK", rec_f32, rec_pq)
""")


@pytest.mark.slow
def test_backend_parity_2shard():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = "src"
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT], env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "BACKEND_PARITY_OK" in r.stdout
