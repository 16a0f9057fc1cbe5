"""JAX production search vs the numpy oracle + baselines + end-to-end API."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import repro.core.search as search_mod
from repro.core import (FavorIndex, SearchConfig, SearchOptions,
                        compile_filter, favor_graph_search, paper_filters,
                        rsf_graph_search, stack_programs)
from repro.core import exclusion
from repro.core import filters as F
from repro.core import refimpl
from repro.core.scoring import scorer_for
from repro.core.search import _merge_pool


def _truth(vecs, mask, q, k):
    return refimpl.bruteforce_filtered(vecs, mask, q, k)[0]


@pytest.fixture(scope="module")
def queries(small_dataset):
    vecs, _, _ = small_dataset
    rng = np.random.default_rng(5)
    return rng.normal(size=(24, vecs.shape[1])).astype(np.float32)


def _setup(small_index, small_dataset, name):
    vecs, attrs, schema = small_dataset
    flt = paper_filters(schema)[name]
    prog = compile_filter(flt, schema)
    mask = F.eval_program(prog, attrs.ints, attrs.floats)
    return flt, prog, mask


@pytest.mark.parametrize("scenario,ef", [("equality_bool", 80),
                                         ("equality_int", 120),
                                         ("inclusion", 80),
                                         ("range_50", 80),
                                         ("logic", 240)])
def test_jax_matches_oracle_recall(small_index, small_dataset, queries, scenario, ef):
    vecs, attrs, schema = small_dataset
    flt, prog, mask = _setup(small_index, small_dataset, scenario)
    p = mask.mean()
    k = 10
    D = float(exclusion.exclusion_distance(p, ef, small_index.delta_d))
    progs = {kk: jnp.asarray(v) for kk, v in
             stack_programs([prog] * len(queries)).items()}
    cfg = SearchConfig(k=k, ef=ef)
    out = favor_graph_search(small_index.g, jnp.asarray(queries), progs,
                             jnp.full((len(queries),), D, jnp.float32), cfg)
    rec_j, rec_o = [], []
    for i, q in enumerate(queries):
        t = _truth(vecs, mask, q, k)
        oid, _, _ = refimpl.favor_search(small_index.index, q, mask, k, ef, D)
        rec_o.append(refimpl.recall_at_k(oid, t, k))
        rec_j.append(refimpl.recall_at_k(np.asarray(out["ids"][i]), t, k))
    assert np.mean(rec_o) >= 0.85, f"oracle recall degraded: {np.mean(rec_o)}"
    # fixed-capacity pools must track the unbounded-heap oracle closely
    assert np.mean(rec_j) >= np.mean(rec_o) - 0.08


def test_search_returns_only_targets(small_index, small_dataset, queries):
    vecs, attrs, schema = small_dataset
    flt, prog, mask = _setup(small_index, small_dataset, "equality_int")
    res = small_index.query(queries, flt, SearchOptions(k=10, ef=80))
    for row in res.ids:
        for v in row[row >= 0]:
            assert mask[v], "non-target row leaked into S"


def test_exclusion_beats_zero_D(small_index, small_dataset, queries):
    """Ablation direction (paper Fig. 10): with D from Eq. 14 the search path
    should touch at least as many targets per hop as with D = 0."""
    vecs, attrs, schema = small_dataset
    flt, prog, mask = _setup(small_index, small_dataset, "equality_int")
    p = mask.mean()
    k, ef = 10, 80
    progs = {kk: jnp.asarray(v) for kk, v in
             stack_programs([prog] * len(queries)).items()}
    cfg = SearchConfig(k=k, ef=ef)
    D = float(exclusion.exclusion_distance(p, ef, small_index.delta_d))
    out_D = favor_graph_search(small_index.g, jnp.asarray(queries), progs,
                               jnp.full((len(queries),), D), cfg)
    out_0 = favor_graph_search(small_index.g, jnp.asarray(queries), progs,
                               jnp.zeros((len(queries),)), cfg)
    frac_D = np.asarray(out_D["path_td"]).sum() / max(1, np.asarray(out_D["hops"]).sum())
    frac_0 = np.asarray(out_0["path_td"]).sum() / max(1, np.asarray(out_0["hops"]).sum())
    assert frac_D >= frac_0 - 0.02


def test_termination_guard_improves_recall(small_index, small_dataset, queries):
    """Section 5.4: pbar_min=0.5 must not lose recall vs pbar_min=0."""
    vecs, attrs, schema = small_dataset
    flt, prog, mask = _setup(small_index, small_dataset, "equality_int")
    k, ef = 10, 40
    r_guard, r_plain = [], []
    res_g = small_index.query(queries, flt, SearchOptions(
        k=k, ef=ef, pbar_min=0.5, force="graph"))
    res_p = small_index.query(queries, flt, SearchOptions(
        k=k, ef=ef, pbar_min=0.0, force="graph"))
    for i, q in enumerate(queries):
        t = _truth(vecs, mask, q, k)
        r_guard.append(refimpl.recall_at_k(res_g.ids[i], t, k))
        r_plain.append(refimpl.recall_at_k(res_p.ids[i], t, k))
    assert np.mean(r_guard) >= np.mean(r_plain) - 1e-9


def test_rsf_baseline_runs(small_index, small_dataset, queries):
    vecs, attrs, schema = small_dataset
    flt, prog, mask = _setup(small_index, small_dataset, "equality_bool")
    progs = {kk: jnp.asarray(v) for kk, v in
             stack_programs([prog] * len(queries)).items()}
    out = rsf_graph_search(small_index.g, jnp.asarray(queries), progs,
                           SearchConfig(k=10, ef=80))
    recs = [refimpl.recall_at_k(np.asarray(out["ids"][i]),
                                _truth(vecs, mask, queries[i], 10), 10)
            for i in range(len(queries))]
    assert np.mean(recs) >= 0.8


def test_selector_routing(small_index, small_dataset, queries):
    vecs, attrs, schema = small_dataset
    lowsel = F.And(F.Equality("i0", 3), F.Range("f0", 10.0, 16.0))  # ~0.6%
    highsel = F.Equality("b0", True)  # 50%
    res = small_index.query(queries[:8], [lowsel] * 4 + [highsel] * 4,
                            SearchOptions(k=5, ef=48))
    assert res.routed_brute[:4].all(), f"low-sel not routed brute: {res.p_hat[:4]}"
    assert not res.routed_brute[4:].any()


def test_brute_route_exact(small_index, small_dataset, queries):
    vecs, attrs, schema = small_dataset
    flt, prog, mask = _setup(small_index, small_dataset, "logic")
    res = small_index.query(queries, flt,
                            SearchOptions(k=10, ef=64, force="brute"))
    for i, q in enumerate(queries):
        t = _truth(vecs, mask, q, 10)
        assert refimpl.recall_at_k(res.ids[i], t, 10) == 1.0


def test_empty_filter_returns_padding(small_index, queries):
    res = small_index.query(queries[:4], F.FalseFilter(),
                            SearchOptions(k=5, ef=48))
    assert (res.ids == -1).all()


def test_save_load_end2end(small_index, small_dataset, queries, tmp_path):
    vecs, attrs, schema = small_dataset
    p = str(tmp_path / "favor")
    small_index.save(p)
    fi2 = FavorIndex.load(p)
    flt = paper_filters(schema)["equality_bool"]
    opts = SearchOptions(k=5, ef=48)
    r1 = small_index.query(queries[:4], flt, opts)
    r2 = fi2.query(queries[:4], flt, opts)
    np.testing.assert_array_equal(r1.ids, r2.ids)


def _argsort_merge(pool, new, cap):
    """The pool merge as a stable argsort and gathers: the oracle that
    ``_merge_pool`` has to match bit for bit."""
    cols = [jnp.concatenate([p, n], axis=1) for p, n in zip(pool, new)]
    order = jnp.argsort(cols[0], axis=1)[:, :cap]
    return tuple(jnp.take_along_axis(c, order, axis=1) for c in cols)


@pytest.mark.parametrize("b,cap,m", [(1, 32, 16), (8, 128, 32), (4, 512, 32)])
def test_merge_pool_matches_argsort_merge(b, cap, m):
    rng = np.random.default_rng(cap + m)
    # sorted pools of few distinct values (many ties) with +inf tails of
    # random length (row 0 full), and an +inf hole where wave.select pops
    # C's minimum; new blocks with ties and +inf runs (ineligible entries)
    pool_d = np.sort(rng.integers(0, 8, (b, cap)), axis=1).astype(np.float32)
    n_fin = rng.integers(cap // 2, cap + 1, b)
    n_fin[0] = cap
    pool_d[np.arange(cap)[None, :] >= n_fin[:, None]] = np.inf
    pool_d[np.arange(b), rng.integers(0, cap, b)] = np.inf
    new_d = rng.integers(0, 8, (b, m)).astype(np.float32)
    new_d[rng.random((b, m)) < 0.3] = np.inf
    pool = (pool_d, rng.integers(-1, 10**6, (b, cap), dtype=np.int32),
            rng.random((b, cap)) < 0.5)
    new = (new_d, rng.integers(-1, 10**6, (b, m), dtype=np.int32),
           rng.random((b, m)) < 0.5)
    merge = jax.jit(_merge_pool, static_argnums=2)
    # R carries (dists, ids, TD flags); C carries (dists, ids)
    for n_cols in (3, 2):
        got = merge(pool[:n_cols], new[:n_cols], cap)
        want = _argsort_merge(pool[:n_cols], new[:n_cols], cap)
        assert len(got) == n_cols
        for g, w in zip(got, want):
            g, w = np.asarray(g), np.asarray(w)
            assert g.dtype == w.dtype and g.shape == w.shape == (b, cap)
            # bit for bit, ids and flags of +inf entries included
            assert np.array_equal(g.view(np.uint8), w.view(np.uint8))
    if b > 1:
        assert np.isinf(np.asarray(want[0])).any()   # +inf payloads compared


@pytest.mark.parametrize("rsf", [False, True])
def test_traversal_matches_argsort_merge(small_index, small_dataset, queries,
                                         monkeypatch, rsf):
    """Same trajectories whichever merge the traversal runs: the served
    entry points against the body traced anew with the argsort merge."""
    flt, prog, mask = _setup(small_index, small_dataset, "equality_int")
    n = len(queries)
    progs = {kk: jnp.asarray(v) for kk, v in
             stack_programs([prog] * n).items()}
    cfg = SearchConfig(k=10, ef=48, use_pallas=False)
    q = jnp.asarray(queries)
    if rsf:
        D = jnp.zeros((n,), jnp.float32)
        got = rsf_graph_search(small_index.g, q, progs, cfg)
    else:
        D = jnp.full((n,), exclusion.exclusion_distance(
            mask.mean(), cfg.ef, small_index.delta_d), jnp.float32)
        got = favor_graph_search(small_index.g, q, progs, D, cfg)
    monkeypatch.setattr(search_mod, "_merge_pool", _argsort_merge)
    # a fresh jit traces the body again, so the oracle merge is in it
    want = jax.jit(lambda g, q, p, D: search_mod._graph_traverse(
        g, q, p, D, cfg, scorer_for(cfg), None, rsf=rsf))(
            small_index.g, q, progs, D)
    assert int(np.asarray(want["waves"])[0]) > 1
    for key in ("ids", "dists", "hops", "path_td", "waves"):
        np.testing.assert_array_equal(np.asarray(got[key]),
                                      np.asarray(want[key]), err_msg=key)
