"""Scorer layer: protocol properties, exclusion composition, the unified
traversal's scorer parity (ADC graph within 1pt of f32; bit-identical with
lossless codes), the rsf lane-mask alignment and the graph_arrays memo."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (BuildSpec, ExactScorer, FavorIndex, HnswParams,
                        LocalBackend, PqAdcScorer, QuantSpec, Scorer,
                        SearchConfig, SearchOptions, SqScorer, compile_filter,
                        exclusion_compose, favor_graph_search, graph_arrays,
                        paper_filters, paper_schema, random_attributes,
                        router, rsf_graph_search, scorer_for, stack_programs)
from repro.core import filters as F
from repro.core import refimpl
from repro.serving import ServeEngine

SCHEMA = paper_schema()
SCORERS = [ExactScorer(), PqAdcScorer(), SqScorer()]


def _quant_g(n=512, d=16, seed=0):
    """A graph-arrays dict carrying every scorer's arrays (pq + sq keys can
    coexist: each scorer reads only its own)."""
    from repro import quant
    rng = np.random.default_rng(seed)
    vecs = rng.normal(size=(n, d)).astype(np.float32)
    attrs = random_attributes(SCHEMA, n, seed=seed + 1)
    fi = FavorIndex.build(vecs, attrs, HnswParams(M=6, efc=32, seed=seed))
    g = dict(fi.g)
    pq = quant.train_pq(vecs, m=8, nbits=6, iters=5, seed=seed)
    sq = quant.train_sq(vecs)
    g["codes"] = jnp.asarray(quant.encode(pq, vecs))
    g["centroids"] = jnp.asarray(pq.centroids)
    g["sq_codes"] = jnp.asarray(quant.encode(sq, vecs))
    g["sq_lo"] = jnp.asarray(sq.lo)
    g["sq_scale"] = jnp.asarray(sq.scale)
    return g, vecs, rng


def _g_for(g, scorer):
    """Swap in the right 'codes' array for the scorer under test."""
    if scorer.kind == "sq":
        g = dict(g)
        g["codes"] = g["sq_codes"]
    return g


def _progs(b, flt=None):
    flt = flt or F.TrueFilter()
    return {k: jnp.asarray(v) for k, v in
            stack_programs([compile_filter(flt, SCHEMA)] * b).items()}


# ---------------------------------------------------------------------------
# Protocol + selection
# ---------------------------------------------------------------------------
def test_scorer_protocol_and_selection():
    for s in SCORERS:
        assert isinstance(s, Scorer)
    assert isinstance(scorer_for(SearchConfig()), ExactScorer)
    assert scorer_for(SearchConfig()).exact
    s = scorer_for(SearchConfig(graph_quant="pq", use_pallas=True))
    assert isinstance(s, PqAdcScorer) and s.use_pallas and not s.exact
    assert isinstance(scorer_for(SearchConfig(graph_quant="sq")), SqScorer)
    # scorers are frozen + hashable: legal jit-static parameters
    assert len({ExactScorer(), ExactScorer(use_pallas=True),
                PqAdcScorer(), SqScorer()}) == 4


def test_bytes_per_row_accounting():
    g, vecs, _ = _quant_g()
    d = vecs.shape[1]
    assert ExactScorer().bytes_per_row(g) == 4 * d
    assert PqAdcScorer().bytes_per_row(g) == 8          # m codes
    assert SqScorer().bytes_per_row(_g_for(g, SqScorer())) == d
    # the graph route's per-hop gather shrinks >= 8x under PQ
    assert ExactScorer().bytes_per_row(g) // PqAdcScorer().bytes_per_row(g) >= 8


@pytest.mark.parametrize("scorer", SCORERS, ids=lambda s: s.kind)
def test_score_block_matches_true_distance(scorer):
    """Every scorer approximates (or equals) the true distance; exact is
    exact."""
    g, vecs, rng = _quant_g()
    gs = _g_for(g, scorer)
    qs = jnp.asarray(rng.normal(size=(4, vecs.shape[1])).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, vecs.shape[0], size=(4, 16), dtype=np.int32))
    state = scorer.prepare(gs, qs, _progs(4))
    d, td = scorer.score_block(gs, state, ids)
    assert td is None          # the jnp scorers leave the filter to the traversal
    d = np.asarray(d)
    true = np.linalg.norm(np.asarray(qs)[:, None, :] - vecs[np.asarray(ids)],
                          axis=-1)
    if scorer.exact:
        np.testing.assert_allclose(d, true, rtol=1e-4, atol=1e-4)
    else:
        # approximate, but correlated: relative error bounded on average
        assert np.mean(np.abs(d - true) / (true + 1e-6)) < 0.25


# ---------------------------------------------------------------------------
# hypothesis properties (CI; the container skips without hypothesis)
# ---------------------------------------------------------------------------
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    _G_CACHE = {}

    def _cached_g():
        if "g" not in _G_CACHE:
            _G_CACHE["g"] = _quant_g(n=256, d=8, seed=5)
        return _G_CACHE["g"]

    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=2 ** 16),
           kind=st.sampled_from(["exact", "pq", "sq"]))
    def test_score_block_permutation_equivariant(seed, kind):
        """Permuting the id block permutes the scores identically: scoring
        is elementwise over ids, for every scorer."""
        g, vecs, _ = _cached_g()
        scorer = {"exact": ExactScorer(), "pq": PqAdcScorer(),
                  "sq": SqScorer()}[kind]
        gs = _g_for(g, scorer)
        rng = np.random.default_rng(seed)
        b, m = 3, 12
        qs = jnp.asarray(rng.normal(size=(b, 8)).astype(np.float32))
        ids = rng.integers(0, vecs.shape[0], size=(b, m), dtype=np.int32)
        perm = rng.permutation(m)
        state = scorer.prepare(gs, qs, _progs(b))
        d = np.asarray(scorer.score_block(gs, state, jnp.asarray(ids))[0])
        dp = np.asarray(
            scorer.score_block(gs, state, jnp.asarray(ids[:, perm]))[0])
        np.testing.assert_array_equal(d[:, perm], dp)

    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=2 ** 16))
    def test_exclusion_compose_preserves_class_order(seed):
        """Eq. 2 adds a per-class constant: within the TD rows (and within
        the non-TD rows) the distance order is untouched, whatever the
        scorer produced."""
        rng = np.random.default_rng(seed)
        m = 32
        d = rng.uniform(0.0, 10.0, size=(1, m)).astype(np.float32)
        td = rng.integers(0, 2, size=(1, m)).astype(bool)
        D = np.float32(rng.uniform(0.0, 20.0))
        dbar = np.asarray(exclusion_compose(jnp.asarray(d), jnp.asarray(td),
                                            jnp.asarray(D)))
        for cls in (td, ~td):
            idx = np.nonzero(cls[0])[0]
            if len(idx) < 2:
                continue
            order_d = idx[np.argsort(d[0, idx], kind="stable")]
            order_b = idx[np.argsort(dbar[0, idx], kind="stable")]
            np.testing.assert_array_equal(order_d, order_b)
        # and every non-TD row is shifted by exactly D
        np.testing.assert_allclose(dbar[~td], d[~td] + D, rtol=1e-6)
        np.testing.assert_array_equal(dbar[td], d[td])


# ---------------------------------------------------------------------------
# Traversal parity across scorers
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def pq_corpus():
    rng = np.random.default_rng(17)
    n, d = 3000, 16
    vecs = rng.normal(size=(n, d)).astype(np.float32)
    attrs = random_attributes(SCHEMA, n, seed=18)
    fi = FavorIndex.build(vecs, attrs, HnswParams(M=8, efc=48, seed=4),
                          BuildSpec(quant=QuantSpec(m=8, nbits=8,
                                                    train_iters=10)))
    queries = rng.normal(size=(24, d)).astype(np.float32)
    return fi, vecs, attrs, queries


@pytest.mark.parametrize("scenario", ["equality_bool", "range_50", "logic"])
def test_pq_graph_recall_within_1pt_of_f32(pq_corpus, scenario):
    """Acceptance bar: the ADC-scored graph route (with exact re-rank) stays
    within 1 recall point of the f32 route."""
    fi, vecs, attrs, queries = pq_corpus
    flt = paper_filters(SCHEMA)[scenario]
    mask = F.eval_program(compile_filter(flt, SCHEMA), attrs.ints, attrs.floats)
    truth = [refimpl.bruteforce_filtered(vecs, mask, q, 10)[0]
             for q in queries]
    be = LocalBackend(fi)
    rec = {}
    for gq in (None, "pq"):
        compiled = favor_graph_search._cache_size()
        res = router.execute(be, queries, flt,
                             SearchOptions(k=10, ef=96, force="graph",
                                           graph_quant=gq))
        # the lane-compaction ladder runs inside one executable per
        # (scorer, program shape): its stage widths are not jit entries
        assert favor_graph_search._cache_size() - compiled <= 1, gq
        rec[gq] = np.mean([refimpl.recall_at_k(res.ids[i], truth[i], 10)
                           for i in range(len(queries))])
    assert rec["pq"] >= rec[None] - 0.01, rec


def test_sq_lossless_codes_bit_identical(pq_corpus):
    """With codes that decode exactly (corpus on the int8 grid), the SQ
    traversal sees the true geometry and the exact re-rank returns the f32
    route's answer bit for bit."""
    rng = np.random.default_rng(23)
    n, d = 1500, 12
    vecs = rng.integers(0, 256, size=(n, d)).astype(np.float32)
    vecs[0], vecs[1] = 0.0, 255.0       # pin the grid: lo=0, scale=1
    attrs = random_attributes(SCHEMA, n, seed=24)
    fi = FavorIndex.build(vecs, attrs, HnswParams(M=8, efc=40, seed=5),
                          BuildSpec(quant=QuantSpec(kind="sq")))
    assert float(np.max(np.abs(
        fi.codebook.scale - 1.0))) == 0.0, "codes not lossless"
    queries = rng.normal(size=(8, d)).astype(np.float32) * 64 + 128
    flt = paper_filters(SCHEMA)["equality_bool"]
    be = LocalBackend(fi)
    r_f32 = router.execute(be, queries, flt,
                           SearchOptions(k=10, ef=64, force="graph"))
    r_sq = router.execute(be, queries, flt,
                          SearchOptions(k=10, ef=64, force="graph",
                                        graph_quant="sq"))
    np.testing.assert_array_equal(r_f32.ids, r_sq.ids)
    np.testing.assert_array_equal(r_f32.dists, r_sq.dists)


def test_pq_bf16_lut_tolerance():
    """bf16 LUT storage halves the per-query table and only perturbs
    distances by the table's rounding error -- not the PQ quantization
    error, which is an order of magnitude larger."""
    g, vecs, rng = _quant_g()
    qs = jnp.asarray(rng.normal(size=(4, vecs.shape[1])).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, vecs.shape[0], size=(4, 16),
                                   dtype=np.int32))
    lo = PqAdcScorer(lut_bf16=True)
    hi = PqAdcScorer(lut_bf16=False)
    st_lo = lo.prepare(g, qs, _progs(4))
    st_hi = hi.prepare(g, qs, _progs(4))
    assert st_lo["luts"].dtype == jnp.bfloat16
    assert st_hi["luts"].dtype == jnp.float32
    assert lo.lut_bytes(g, 4) * 2 == hi.lut_bytes(g, 4)
    d_lo = np.asarray(lo.score_block(g, st_lo, ids)[0])
    d_hi = np.asarray(hi.score_block(g, st_hi, ids)[0])
    np.testing.assert_allclose(d_lo, d_hi, rtol=2e-2)
    assert np.mean(np.abs(d_lo - d_hi) / (d_hi + 1e-6)) < 5e-3


def test_sq_score_block_bit_stable_across_batch_width():
    """Lane compaction re-invokes the scorer at every stage width, so a
    lane's distances must not depend on how many other lanes ride along --
    the folded-affine SQ path keeps its contractions batch-independent."""
    g, vecs, rng = _quant_g()
    sc = SqScorer()
    gs = _g_for(g, sc)
    qs = jnp.asarray(rng.normal(size=(8, vecs.shape[1])).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, vecs.shape[0], size=(8, 16),
                                   dtype=np.int32))
    full_state = sc.prepare(gs, qs, _progs(8))
    full = np.asarray(sc.score_block(gs, full_state, ids)[0])
    for width in (1, 2, 4):
        for off in range(0, 8, width):
            sl = slice(off, off + width)
            st = sc.prepare(gs, qs[sl], _progs(width))
            part = np.asarray(sc.score_block(gs, st, ids[sl])[0])
            np.testing.assert_array_equal(part, full[sl])


def test_sq_graph_route_matches_singles_under_compaction(pq_corpus):
    """Regression for the compaction ladder slicing scorer state: SqScorer's
    query-independent w2 weights are declared shared_state and must survive
    lane packing -- a batched run equals 24 independent single-query runs
    bit for bit."""
    fi, vecs, attrs, queries = pq_corpus
    fi_sq = FavorIndex(fi.index, attrs, BuildSpec(quant=QuantSpec(kind="sq")))
    be = LocalBackend(fi_sq)
    flt = paper_filters(SCHEMA)["equality_bool"]
    opts = SearchOptions(k=10, ef=48, force="graph", graph_quant="sq")
    batched = router.execute(be, queries[:6], flt, opts)
    for i in range(6):
        single = router.execute(be, queries[i:i + 1], flt, opts)
        np.testing.assert_array_equal(single.ids[0], batched.ids[i])
        np.testing.assert_array_equal(single.dists[0], batched.dists[i])


def test_max_steps_budget(pq_corpus):
    """SearchOptions.max_steps bounds total traversal waves across the
    compaction ladder; capped lanes still return a valid result pool."""
    fi, vecs, attrs, queries = pq_corpus
    be = LocalBackend(fi)
    flt = paper_filters(SCHEMA)["equality_bool"]
    free = router.execute(be, queries, flt,
                          SearchOptions(k=10, ef=96, force="graph",
                                        graph_quant="pq"))
    cap = int(np.max(free.waves)) // 2
    capped = router.execute(be, queries, flt,
                            SearchOptions(k=10, ef=96, force="graph",
                                          graph_quant="pq", max_steps=cap))
    assert int(np.max(capped.waves)) <= cap
    assert (capped.ids >= 0).any(axis=1).all()   # every lane returned hits
    assert np.isfinite(capped.dists[capped.ids >= 0]).all()
    with pytest.raises(ValueError):
        SearchOptions(max_steps=-1)


def test_graph_quant_padded_parity(pq_corpus):
    """Bucket padding stays bit-identical under the quantized scorer."""
    from repro.core import BatchSpec
    fi, vecs, attrs, queries = pq_corpus
    flt = paper_filters(SCHEMA)["equality_bool"]
    be = LocalBackend(fi)
    opts = SearchOptions(k=10, ef=64, force="graph", graph_quant="pq")
    ra = router.execute(be, queries[:5], flt, opts)
    rb = router.execute(be, queries[:5], flt,
                        opts.with_(batch=BatchSpec(min_bucket=4,
                                                   max_bucket=32)))
    np.testing.assert_array_equal(ra.ids, rb.ids)
    np.testing.assert_array_equal(ra.dists, rb.dists)
    np.testing.assert_array_equal(ra.hops, rb.hops)


def test_rsf_valid_mask_and_path_td(pq_corpus):
    """Satellite: rsf_graph_search carries the same lane-mask contract and
    diagnostics as favor_graph_search (one traversal body)."""
    fi, vecs, attrs, queries = pq_corpus
    flt = paper_filters(SCHEMA)["equality_bool"]
    progs = {k: jnp.asarray(v) for k, v in stack_programs(
        [compile_filter(flt, SCHEMA)] * 8).items()}
    cfg = SearchConfig(k=10, ef=48)
    full = rsf_graph_search(fi.g, jnp.asarray(queries[:8]), progs, cfg)
    assert "path_td" in full and "hops" in full
    valid = np.array([True] * 5 + [False] * 3)
    masked = rsf_graph_search(fi.g, jnp.asarray(queries[:8]), progs, cfg,
                              valid=jnp.asarray(valid))
    np.testing.assert_array_equal(np.asarray(masked["ids"])[:5],
                                  np.asarray(full["ids"])[:5])
    np.testing.assert_array_equal(np.asarray(masked["dists"])[:5],
                                  np.asarray(full["dists"])[:5])
    assert (np.asarray(masked["ids"])[5:] == -1).all()
    assert np.isinf(np.asarray(masked["dists"])[5:]).all()
    assert (np.asarray(masked["hops"])[5:] == 0).all()
    assert (np.asarray(masked["path_td"])[5:] == 0).all()


def test_engine_warmup_and_stats_with_graph_quant(pq_corpus):
    from repro.core import BatchSpec
    fi, vecs, attrs, queries = pq_corpus
    eng = ServeEngine(LocalBackend(fi),
                      SearchOptions(k=10, ef=48, graph_quant="pq",
                                    batch=BatchSpec(min_bucket=4,
                                                    max_bucket=8)))
    eng.warmup()
    assert eng.stats["scorers"]["graph"] == "pq"
    assert eng.stats["scorers"]["brute"] == "exact"
    flt = paper_filters(SCHEMA)["equality_bool"]
    for q in queries[:5]:
        eng.submit(q, flt)
    out = eng.run()
    assert len(out) == 5


def test_graph_route_pallas_scorers_match_jnp():
    """use_pallas wires the graph route through the kernels (gather_distance
    for exact, the pq_adc block-gather for PQ): same answers as the jnp
    scorers.  Tiny corpus -- interpret-mode kernels run per hop."""
    rng = np.random.default_rng(9)
    n, d = 400, 16
    vecs = rng.normal(size=(n, d)).astype(np.float32)
    attrs = random_attributes(SCHEMA, n, seed=2)
    fi = FavorIndex.build(vecs, attrs, HnswParams(M=6, efc=32, seed=1),
                          BuildSpec(quant=QuantSpec(m=4, nbits=6,
                                                    train_iters=5)))
    qs = rng.normal(size=(2, d)).astype(np.float32)
    flt = paper_filters(SCHEMA)["equality_bool"]
    be = LocalBackend(fi)
    for gq in (None, "pq"):
        base = SearchOptions(k=5, ef=24, force="graph", graph_quant=gq)
        rj = router.execute(be, qs, flt, base)
        rp = router.execute(be, qs, flt, base.with_(use_pallas=True))
        np.testing.assert_array_equal(rj.ids, rp.ids), gq
        np.testing.assert_allclose(rj.dists, rp.dists, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Side rows: one read of a block's scalars, one filter evaluation
# ---------------------------------------------------------------------------
# attribute values sit exactly on the range's bounds, and the largest int
# code is one the filter asks for; two DNF terms, so W > 1
SIDE_FILTER = F.Or(
    F.And(F.Inclusion("i0", [2, 9]), F.Range("f0", 10.0, 60.0)),
    F.And(F.Equality("b0", True), F.Range("f0", 60.0, 85.0)))


@pytest.fixture(scope="module")
def side_corpus():
    from repro import quant
    rng = np.random.default_rng(23)
    n, d = 400, 16
    vecs = rng.normal(size=(n, d)).astype(np.float32)
    attrs = random_attributes(SCHEMA, n, seed=24)
    i0 = SCHEMA.int_columns.index(
        next(c for c in SCHEMA.int_columns if c.name == "i0"))
    attrs.ints[::5, i0] = 9                       # the vocabulary's last code
    attrs.floats[::7, 0] = 10.0                   # on flo
    attrs.floats[3::7, 0] = 60.0                  # on fhi of one term, flo
    attrs.floats[5::7, 0] = 85.0                  # of the other; fhi
    fi = FavorIndex.build(vecs, attrs, HnswParams(M=6, efc=32, seed=2))
    g = dict(fi.g)
    pq = quant.train_pq(vecs, m=4, nbits=6, iters=5, seed=1)
    sq = quant.train_sq(vecs)
    g["codes"] = jnp.asarray(quant.encode(pq, vecs))
    g["centroids"] = jnp.asarray(pq.centroids)
    g["sq_codes"] = jnp.asarray(quant.encode(sq, vecs))
    g["sq_lo"] = jnp.asarray(sq.lo)
    g["sq_scale"] = jnp.asarray(sq.scale)
    queries = jnp.asarray(rng.normal(size=(4, d)).astype(np.float32))
    return g, fi.delta_d, queries


def _two_read_oracle(monkeypatch):
    """The traversal as it read a block's scalars before the side rows:
    each norm, attribute and alive flag through a gather of its own array
    at the row ids, and the filter evaluated by eval_program_gathered
    whatever the scorer returns.  The "side row" becomes the row id."""
    from repro.core import scoring as scoring_mod
    from repro.core import search as search_mod

    def table(g):
        return jnp.arange(g["norms"].shape[0], dtype=jnp.int32)[:, None]

    def fields(g, rows):
        ids = rows[..., 0]
        return (g["norms"][ids], g["attrs_int"][ids], g["attrs_float"][ids],
                g["alive"][ids] if "alive" in g else None)

    evaluate = search_mod._td
    monkeypatch.setattr(search_mod, "side_table", table)
    monkeypatch.setattr(search_mod, "side_fields", fields)
    monkeypatch.setattr(scoring_mod, "side_fields", fields)
    monkeypatch.setattr(search_mod, "_td",
                        lambda g, programs, rows, td=None:
                        evaluate(g, programs, rows))
    return search_mod._graph_traverse


@pytest.mark.parametrize("tombstones", [False, True], ids=["static", "dead"])
@pytest.mark.parametrize("rsf", [False, True], ids=["favor", "rsf"])
@pytest.mark.parametrize("kind", ["exact", "exact_pallas", "pq", "sq"])
def test_side_rows_traversal_bit_parity(side_corpus, monkeypatch, kind, rsf,
                                        tombstones):
    """Reading a block's norms, attributes and alive flags from the packed
    side rows, and taking the Pallas kernel's TD mask, leave every result
    of the traversal bit for bit as the separate reads and the second
    filter evaluation gave, for every scorer, mode and tombstone state.
    The Pallas kernel's mask equals eval_program_gathered on the block."""
    g0, delta_d, q = side_corpus
    cfg = {"exact": SearchConfig(k=5, ef=24),
           "exact_pallas": SearchConfig(k=5, ef=24, use_pallas=True),
           "pq": SearchConfig(k=5, ef=24, graph_quant="pq"),
           "sq": SearchConfig(k=5, ef=24, graph_quant="sq")}[kind]
    scorer = scorer_for(cfg)
    g = dict(_g_for(g0, scorer))          # the fixture's dict stays as is
    if tombstones:
        alive = np.ones(g["norms"].shape[0], bool)
        alive[1::3] = False
        g["alive"] = jnp.asarray(alive)
    b = q.shape[0]
    progs = _progs(b, SIDE_FILTER)
    sel = float(np.mean(np.asarray(F.eval_program_gathered(
        progs, g["attrs_int"][None], g["attrs_float"][None], xp=jnp))))
    assert 0.1 < sel < 0.5
    if rsf:
        D = jnp.zeros((b,), jnp.float32)
        got = rsf_graph_search(g, q, progs, cfg)
    else:
        from repro.core import exclusion
        D = jnp.full((b,), exclusion.exclusion_distance(
            sel, cfg.ef, delta_d), jnp.float32)
        got = favor_graph_search(g, q, progs, D, cfg)

    if kind == "exact_pallas":
        ids = jnp.asarray(np.random.default_rng(5).integers(
            0, g["norms"].shape[0], size=(b, 32), dtype=np.int32))
        _, td = scorer.score_block(g, scorer.prepare(g, q, progs), ids)
        want_td = F.eval_program_gathered(progs, g["attrs_int"][ids],
                                          g["attrs_float"][ids], xp=jnp)
        np.testing.assert_array_equal(np.asarray(td), np.asarray(want_td))
        assert 0 < int(np.sum(np.asarray(td))) < td.size

    traverse = _two_read_oracle(monkeypatch)
    # a fresh jit traces the body again, with the oracle's reads in it
    want = jax.jit(lambda g, q, p, D: traverse(
        g, q, p, D, cfg, scorer, None, rsf=rsf))(g, q, progs, D)
    assert int(np.asarray(want["waves"])[0]) > 1
    assert (np.asarray(want["ids"]) >= 0).any()
    for key in ("ids", "dists", "hops", "path_td", "waves"):
        np.testing.assert_array_equal(np.asarray(got[key]),
                                      np.asarray(want[key]), err_msg=key)


# ---------------------------------------------------------------------------
# graph_arrays memoization
# ---------------------------------------------------------------------------
def test_graph_arrays_memoized(small_index, small_dataset):
    _, attrs, _ = small_dataset
    g1 = graph_arrays(small_index.index, attrs)
    g2 = graph_arrays(small_index.index, attrs)
    assert g1 is g2                      # same (index, attrs, version) -> hit
    assert g1["vectors"] is g2["vectors"]
    g3 = graph_arrays(small_index.index, attrs, version=1)
    assert g3 is not g1                  # version bump -> fresh upload
    # FavorIndex holds a *copy*: adding quantized-scorer keys there must
    # never leak into the shared cache entry
    fi2 = FavorIndex(small_index.index, attrs,
                     BuildSpec(quant=QuantSpec(m=4, nbits=4, train_iters=4)))
    assert "codes" in fi2.g
    assert "codes" not in graph_arrays(small_index.index, attrs)
    assert fi2.g["vectors"] is g1["vectors"]  # arrays still shared


def test_bump_version_reuploads_attrs(small_dataset):
    """An in-place attribute edit + bump_version() must reach the device
    copies (the memo is keyed on the epoch), and the scorer arrays ride
    along."""
    vecs, attrs0, schema = small_dataset
    attrs = F.AttributeTable(schema, attrs0.ints.copy(), attrs0.floats.copy())
    fi = FavorIndex.build(vecs[:600], F.AttributeTable(
        schema, attrs.ints[:600], attrs.floats[:600]),
        HnswParams(M=6, efc=32, seed=8),
        BuildSpec(quant=QuantSpec(m=4, nbits=4, train_iters=4)))
    fi.attrs.ints[:] = (fi.attrs.ints + 1) % 2
    # (whether the pre-bump device copy aliases the host buffer is an XLA
    # CPU implementation detail -- the contract is only post-bump freshness)
    fi.bump_version()
    np.testing.assert_array_equal(np.asarray(fi.g["attrs_int"]),
                                  fi.attrs.ints)
    assert "codes" in fi.g and "centroids" in fi.g  # scorer arrays restored
