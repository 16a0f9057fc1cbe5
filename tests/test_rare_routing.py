"""Rare filters on the served path: the selector's exact check near lambda,
and the PQ brute route (32 sub-quantisers of 8 bits, re-rank 4) against the
exact float32 reference, at the widths of the ``sift128-pq`` deployment.

The brute route never reads the graph, so its index here carries an empty
one (no neighbour of any node): no host HNSW build, and a request the
selector wrongly sent to the graph would come back empty.
"""
import numpy as np
import pytest

from repro.core import (BatchSpec, BuildSpec, FavorIndex, HnswIndex,
                        HnswParams, LocalBackend, QuantSpec, SearchOptions,
                        paper_filters, paper_schema, random_attributes)
from repro.core import filters as F
from repro.core import refimpl, router, selector
from repro.data.synthetic import make_queries, make_vector_dataset
from repro.serving import ServeEngine

SCHEMA = paper_schema()
N, DIM = 8192, 128
K = 10
LAM = selector.SelectorConfig().lam
# recall@10 of the PQ route with re-rank 4 read 1.0 on every seed tried on
# the CPU (5, 11, 23, 31: 64 queries each); with re-rank 0 -- the ADC top-k
# alone, no exact re-rank depth -- it read 0.875-0.889.  The bound sits
# between, closer to the sound runs.
RECALL_BOUND = 0.97


def _empty_graph_index(vecs, attrs, quant=None) -> FavorIndex:
    p = HnswParams(M=8)
    n = vecs.shape[0]
    g = HnswIndex(vecs, [np.full((n, p.M0), -1, np.int32)],
                  np.zeros(n, np.int16), 0, 0, 1.0, p)
    return FavorIndex(g, attrs, BuildSpec(hnsw=p, quant=quant))


@pytest.fixture(scope="module", params=[5, 23])
def pq_case(request):
    """The deployment's widths at a small N: d = 128, PQ m = 32 of 8 bits,
    re-rank 4 (R = 40); a Gaussian-mixture corpus, seeded attributes."""
    seed = request.param
    vecs = make_vector_dataset(N, DIM, seed=seed)
    attrs = random_attributes(SCHEMA, N, seed=seed + 1)
    fi = _empty_graph_index(vecs, attrs,
                            QuantSpec(kind="pq", m=32, nbits=8, rerank=4))
    return seed, vecs, attrs, fi


def _rare_filters(attrs, seed, count, *, lo_w=0.1, hi_w=1.0):
    """``count`` f0 ranges whose exact selectivity is in (0, lambda)."""
    rng = np.random.default_rng(seed)
    flts, masks = [], []
    while len(flts) < count:
        w = float(rng.uniform(lo_w, hi_w))
        lo = float(rng.uniform(0.0, 100.0 - w))
        f = F.Range("f0", lo, lo + w)
        m = F.eval_program(F.compile_filter(f, SCHEMA), attrs.ints,
                           attrs.floats)
        if 0 < m.sum() < LAM * len(m):
            flts.append(f)
            masks.append(np.asarray(m, bool))
    return flts, masks


def _sampled(fi, flt) -> float:
    """p_hat of ``flt`` over the index's own selectivity sample."""
    m = F.eval_program(F.compile_filter(flt, SCHEMA),
                       fi.attrs.ints[fi.sample_idx],
                       fi.attrs.floats[fi.sample_idx])
    return float(np.mean(m))


# ---------------------------------------------------------------------------
# the check band
# ---------------------------------------------------------------------------
def test_check_band_is_four_to_fourteen_hits_at_the_cell_sample():
    """At the 328-row sample of 2^15 rows: an estimate of 4 to 14 hits is
    checked; 3 hits routes brute on the sample, 15 graph on the sample."""
    n, total = 328, 32768
    hits = np.arange(0, 40)
    band = selector.check_band(hits / n, n, total, LAM)
    assert np.array_equal(np.nonzero(band)[0], np.arange(4, 15))
    # no sample (an empty base): nothing to bound, nothing checked
    assert not selector.check_band(hits / n, 0, 0, LAM).any()


def test_relative_error_takes_arrays():
    from repro.core import selectivity
    p = np.array([0.0, 0.01, 0.5])
    err = selectivity.relative_error(10000, p, 1_000_000)
    assert np.isinf(err[0])
    assert err[1] == pytest.approx(selectivity.relative_error(
        10000, 0.01, 1_000_000))
    assert isinstance(selectivity.relative_error(10000, 0.5, 1_000_000),
                      float)


@pytest.mark.parametrize("cached", [False, True])
def test_sub_lambda_filters_read_above_lambda_go_brute(pq_case, cached):
    """Filters of 0.1-0.8% whose sample reads p_hat >= lambda -- which the
    estimate alone sends to the graph -- are checked and go brute, also
    through the cache layer with tenant scopes on the programs."""
    from repro.cache import CachingBackend
    from repro.core import CacheSpec
    seed, vecs, attrs, fi = pq_case
    rng = np.random.default_rng(seed + 7)
    flts = []
    while len(flts) < 8:
        w = float(rng.uniform(0.1, 0.8))
        lo = float(rng.uniform(0.0, 100.0 - w))
        f = F.Range("f0", lo, lo + w)
        m = F.eval_program(F.compile_filter(f, SCHEMA), attrs.ints,
                           attrs.floats)
        if m.mean() < LAM and _sampled(fi, f) >= LAM:
            flts.append(f)
    qs = make_queries(len(flts), DIM, dataset_seed=seed, seed=seed + 8)
    be = LocalBackend(fi)
    if cached:
        be = CachingBackend(be, CacheSpec())
    res = router.execute(be, qs, flts, SearchOptions(k=K, use_pq=True),
                         scopes=np.arange(1, len(flts) + 1))
    assert (res.p_hat >= LAM).all()
    assert not selector.route(res.p_hat, LAM).any()   # estimate alone: graph
    assert res.routed_brute.all() and res.checked.all()
    assert (res.ids >= 0).sum(axis=1).min() > 0


@pytest.mark.parametrize("attr_seed", range(6))
def test_paper_filters_route_as_the_estimate_did(small_index, attr_seed):
    """The six paper filters (5-50%) route exactly as the estimate-only rule
    routed them, checked or not: a true selectivity at or above lambda
    keeps its route."""
    attrs = random_attributes(SCHEMA, small_index.index.n,
                              seed=100 + attr_seed)
    fi = FavorIndex(small_index.index, attrs)
    flts = list(paper_filters(SCHEMA, np.random.default_rng(attr_seed))
                .values())
    for f in flts:
        m = F.eval_program(F.compile_filter(f, SCHEMA), attrs.ints,
                           attrs.floats)
        assert m.mean() >= LAM
    qs = np.random.default_rng(attr_seed).normal(
        size=(len(flts), small_index.index.dim)).astype(np.float32)
    res = router.execute(LocalBackend(fi), qs, flts,
                         SearchOptions(k=K, ef=32, batch=BatchSpec(
                             min_bucket=8, max_bucket=8)))
    assert np.array_equal(res.routed_brute, selector.route(res.p_hat, LAM))
    assert not res.routed_brute.any()


def test_paper_filters_do_reach_the_band(small_index):
    """The six-seed sweep above is not vacuous: the logic filter's estimate
    falls in the check band on some of its seeds."""
    n, total = LocalBackend(small_index).sample_size
    hit = 0
    for s in range(6):
        attrs = random_attributes(SCHEMA, total, seed=100 + s)
        fi = FavorIndex(small_index.index, attrs)
        logic = paper_filters(SCHEMA, np.random.default_rng(s))["logic"]
        hit += bool(selector.check_band(np.array([_sampled(fi, logic)]),
                                        n, total, LAM)[0])
    assert hit >= 1


def test_empty_band_costs_no_dispatch(pq_case, monkeypatch):
    """Estimates outside the band never call the exact count; a batch with
    band rows calls it once, for every band row together."""
    seed, vecs, attrs, fi = pq_case
    calls = []
    orig = LocalBackend.exact_selectivity

    def spy(self, programs, valid=None):
        calls.append(int(programs["valid"].shape[0]))
        return orig(self, programs, valid=valid)

    monkeypatch.setattr(LocalBackend, "exact_selectivity", spy)
    be = LocalBackend(fi)
    far = [F.Equality("b0", True), F.Range("f0", 1.0, 1.001),
           F.Inclusion("i0", [1, 2, 3])]
    qs = make_queries(len(far), DIM, dataset_seed=seed, seed=3)
    res = router.execute(be, qs, far, SearchOptions(k=K, use_pq=True))
    assert calls == [] and not res.checked.any()
    band, _ = _rare_filters(attrs, seed + 9, 40, lo_w=0.6, hi_w=0.99)
    band = [f for f in band if _sampled(fi, f) >= LAM][:3]
    assert band
    flts = far + band
    qs = make_queries(len(flts), DIM, dataset_seed=seed, seed=4)
    opts = SearchOptions(k=K, use_pq=True,
                         batch=BatchSpec(min_bucket=8, max_bucket=8))
    res = router.execute(be, qs, flts, opts)
    assert calls == [8]                       # one call, bucket-padded
    assert np.array_equal(res.checked, [False] * len(far) + [True] * len(band))
    # a pinned route is never checked
    res = router.execute(be, qs, flts, opts.with_(force="brute"))
    assert calls == [8] and not res.checked.any()


def test_check_compiles_nothing_per_band_size(pq_case):
    """After warm-up, batches whose check bands hold 1, 2 or 3 rows compile
    nothing: the count runs on the estimate's own bucket-shaped batch."""
    from repro.core import batching
    from repro.obs import MetricsRegistry, count_compiles
    seed, vecs, attrs, fi = pq_case
    flts, _ = _rare_filters(attrs, seed + 13, 120, lo_w=0.1, hi_w=0.99)
    band = [f for f in flts if _sampled(fi, f) >= LAM][:3]
    quiet = [f for f in flts if _sampled(fi, f) < LAM][:8]
    assert len(band) == 3 and len(quiet) == 8
    be = LocalBackend(fi)
    opts = SearchOptions(k=K, use_pq=True,
                         batch=BatchSpec(min_bucket=8, max_bucket=8))
    batching.warmup(be, opts)
    qs = make_queries(8, DIM, dataset_seed=seed, seed=6)
    router.execute(be, qs, quiet, opts)          # eager first-use ops
    reg = MetricsRegistry()
    count_compiles(reg)

    def compiles():
        series = reg.snapshot()["counters"].get(
            "favor_xla_compiles_total", {}).get("series", {})
        return sum(series.values())

    for m in (1, 2, 3):
        res = router.execute(be, qs, band[:m] + quiet[m:], opts)
        assert res.checked.sum() == m and res.routed_brute.all()
    assert compiles() == 0


def test_engine_counts_the_requests_the_check_moved(pq_case):
    seed, vecs, attrs, fi = pq_case
    band, _ = _rare_filters(attrs, seed + 11, 60, lo_w=0.6, hi_w=0.99)
    band = [f for f in band if _sampled(fi, f) >= LAM][:4]
    flts = band + [F.Range("f0", 10.0, 10.3)] * 2
    eng = ServeEngine(LocalBackend(fi), SearchOptions(k=K, use_pq=True),
                      max_batch=8)
    qs = make_queries(len(flts), DIM, dataset_seed=seed, seed=5)
    for q, f in zip(qs, flts):
        eng.submit(q, f)
    out = eng.run()
    assert [r.route for r in out] == ["brute"] * len(flts)
    series = eng.obs.snapshot()["counters"]["favor_route_checked_total"][
        "series"]
    assert series == {'outcome="brute"': float(len(band))}


# ---------------------------------------------------------------------------
# the PQ brute route against the exact float32 reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("rerank", [4, 0])
def test_pq_brute_route_against_exact_reference(pq_case, rerank):
    """Served ``LocalBackend`` with ``use_pq``, filters of 0.1-1%: answers
    pass their filter, come sorted, carry their rows' exact float32
    distances, and reach ``RECALL_BOUND`` -- which the ``rerank = 0``
    fault (no exact re-rank depth beyond k) fails."""
    seed, vecs, attrs, fi = pq_case
    flts, masks = _rare_filters(attrs, seed + 3, 64)
    qs = make_queries(len(flts), DIM, dataset_seed=seed, seed=seed + 2)
    res = router.execute(LocalBackend(fi), qs, flts,
                         SearchOptions(k=K, use_pq=True, rerank=rerank))
    assert res.routed_brute.all()
    eps = np.finfo(np.float32).eps
    recall = []
    for i in range(len(flts)):
        ids = res.ids[i][res.ids[i] >= 0]
        d = res.dists[i][:len(ids)]
        # the reference: refimpl's exact filtered brute force in float32
        t_ids, _ = refimpl.bruteforce_filtered(vecs, masks[i], qs[i], K)
        assert len(ids) == min(K, int(masks[i].sum()))
        assert masks[i][ids].all()
        assert np.all(np.diff(d) >= 0)
        exact = np.linalg.norm(vecs[ids] - qs[i][None, :], axis=1)
        # the route computes |x|^2 + |q|^2 - 2 x.q in float32: each term a
        # DIM-long float32 sum off by at most (DIM + 3) eps of the
        # magnitudes it adds (Higham's gamma bound), and a squared distance
        # off by e moves the distance by at most e / d
        xn = np.einsum("nd,nd->n", vecs[ids], vecs[ids])
        qn = float(qs[i] @ qs[i])
        tol = (DIM + 3) * eps * (xn + qn + 2.0 * np.sqrt(xn * qn)) / exact
        assert np.all(np.abs(d - exact) <= tol), (i, d - exact, tol)
        recall.append(refimpl.recall_at_k(ids, t_ids, K))
    if rerank == 4:
        assert np.mean(recall) >= RECALL_BOUND
    else:
        assert np.mean(recall) < RECALL_BOUND
