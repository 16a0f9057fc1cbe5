"""Tests of the benchmark harness, on the CPU at a tiny size.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They check the harness itself -- that every cell's files load, the load
generators, the result line, the trace reduction, the roofline counts --
and that ``correct`` comes out false for the control and for a served path
broken underneath.  No number here is a device measurement.
"""
from __future__ import annotations

import asyncio
import io
import json
import math
import os
import subprocess
import sys
from contextlib import redirect_stdout
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import index_cache  # noqa: E402
import layer  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import serve  # noqa: E402
import trace_reduce  # noqa: E402
import workload  # noqa: E402

SPEC = workload.load_json(ROOT / "BENCHMARK.json")
CELLS = [w["name"] for w in SPEC["workloads"]]
SEED = 2**31 + 12345            # larger than 32 signed bits hold
TINY_CORPUS = {"generator": "gaussian_mixture", "n": 1024, "dim": 128,
               "clusters": 32, "cluster_std": 0.35, "centers_seed": 0,
               "sample_seed": 1, "metric": "l2"}


def tiny(cell: str) -> dict:
    """Overrides that shrink a cell to a few seconds on the CPU: the jnp
    path in place of the Pallas kernels (interpret mode is slow there)."""
    cfg = workload.load_cell(cell).config
    ov = {"corpus": TINY_CORPUS, "hnsw": {"M": 16, "efc": 32, "seed": 0},
          "pool": 64, "batch": {"min_bucket": 16, "max_bucket": 16},
          "engine": {"max_batch": 16},
          "search": dict(cfg["search"], use_pallas=False)}
    if cfg.get("quant"):
        ov["quant"] = dict(cfg["quant"], train_iters=2)
    if "closed" in cell:
        ov["clients"] = 32
    else:
        ov["rate"] = 100.0
    return ov


@pytest.fixture
def cache(tmp_path, monkeypatch):
    monkeypatch.setattr(index_cache, "CACHE", tmp_path / "index")
    return tmp_path


def run_cell(cell: str, trace: int = 0, seconds: float = 1.0) -> tuple[int, dict | None, str]:
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = run.main(["--workload", cell, "--seed", str(SEED), "--seconds",
                       str(seconds), "--trace", str(trace)],
                      expect_platform="cpu", overrides=tiny(cell))
    out = buf.getvalue().strip().splitlines()
    last = json.loads(out[-1]) if out and out[-1].startswith("{") else None
    return rc, last, "\n".join(out)


# ---------------------------------------------------------------------------
# Files found by name
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_load(cell):
    c = workload.load_cell(cell)
    assert c.chips == 1
    assert set(c.limits) == {"unanswered", "bad_answers", "recall_miss",
                             "dist_err"}
    assert c.traffic["loop"] in ("closed", "open")
    assert c.config["corpus"]["n"] > 0


def test_every_metric_has_a_reader_and_every_config_a_cell():
    for m in SPEC["per_layer"]:
        assert callable(run.load_reader(m["name"])), m["name"]
        assert set(m["workloads"]) <= set(CELLS)
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).is_file()
    for w in SPEC["workloads"]:
        names = {m["name"] for m in run.metric_specs(w["name"], "end_to_end")}
        assert "setup_s" in names and len(names) >= 2
        assert run.metric_specs(w["name"], "per_layer")


# ---------------------------------------------------------------------------
# Inputs from the seed
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("cell", CELLS)
def test_pool_is_a_function_of_the_seed(cell):
    c = workload.load_cell(cell, overrides={"corpus": TINY_CORPUS})
    a = workload.make_pool(c.config, c.traffic, SEED)
    b = workload.make_pool(c.config, c.traffic, SEED)
    other = workload.make_pool(c.config, c.traffic, SEED + 1)
    assert np.array_equal(a.queries, b.queries) and a.filters == b.filters
    assert not np.array_equal(a.queries, other.queries)
    assert a.size == c.traffic["pool"]
    for f in a.filters:
        for leaf in _leaves(f):
            for key in ("lo", "hi"):
                if key in leaf:
                    assert leaf[key] * workload.GRID == int(leaf[key] * workload.GRID)


def _leaves(f):
    if f["op"] == "and":
        for c in f["children"]:
            yield from _leaves(c)
    else:
        yield f


@pytest.mark.parametrize("cell", CELLS)
def test_filters_agree_with_the_program(cell):
    from repro.core import filters as F
    c = workload.load_cell(cell, overrides={"corpus": TINY_CORPUS})
    cfg = c.config
    ints, floats = workload.make_attributes(cfg, SEED)
    pool = workload.make_pool(cfg, c.traffic, SEED)
    cols = workload.column_index(cfg)
    schema = workload.program_schema(cfg)
    for f in pool.filters[:50]:
        ours = workload.eval_filter(f, ints, floats, cols)
        prog = F.eval_program(F.compile_filter(workload.to_program_filter(f),
                                               schema), ints, floats)
        assert np.array_equal(ours, np.asarray(prog, bool))


def test_arrivals_same_gaps_any_seed():
    traffic = {"rate": 500.0, "arrival_seed": 3}
    a = workload.arrival_times(traffic, 4.0, SEED)
    b = workload.arrival_times(traffic, 4.0, SEED + 7)
    assert len(a) == len(b) == 2000
    assert a[0] == 0.0 and np.all(np.diff(a) >= 0) and a[-1] < 4.0
    assert not np.array_equal(a, b)
    gaps = lambda t: np.sort(np.append(np.diff(t), 4.0 - t[-1]))  # noqa: E731
    np.testing.assert_allclose(gaps(a), gaps(b), rtol=1e-9, atol=1e-12)


class FakeFrontEnd:
    """Answers after ``delay`` seconds, like FrontEnd.submit."""

    def __init__(self, delay: float):
        self.delay = delay
        self.inflight = self.peak = 0

    async def submit(self, q, flt):
        self.inflight += 1
        self.peak = max(self.peak, self.inflight)
        await asyncio.sleep(self.delay)
        self.inflight -= 1
        return SimpleNamespace(ids=np.arange(10), dists=np.arange(10.0),
                               route="graph")


def _fake_sub(delay):
    pool = SimpleNamespace(size=8, queries=np.zeros((8, 4), np.float32),
                           filter_of=np.zeros(8, np.int64),
                           filters=[{"op": "eq", "col": "i0", "value": 1}])
    sub = serve.Submitter.__new__(serve.Submitter)
    sub.fe, sub.pool, sub.log = FakeFrontEnd(delay), pool, serve.Requests(10)
    sub._shed, sub._flts = RuntimeError, [None]
    return sub


def test_closed_loop_keeps_clients_busy():
    sub = _fake_sub(0.01)
    t0, t_end = asyncio.run(serve.closed_loop(sub, 8, 0.3, SEED))
    assert sub.fe.peak == 8
    done = np.asarray(sub.log.done)
    assert np.all(np.asarray(sub.log.sent) < t_end)
    assert 8 * 0.3 / 0.01 * 0.5 < np.sum(done <= t_end) <= 8 * 0.3 / 0.01 + 8
    assert all(s == "ok" for s in sub.log.status)


def test_open_loop_sends_on_schedule():
    sub = _fake_sub(0.05)
    times = workload.arrival_times({"rate": 200.0, "arrival_seed": 1}, 0.5, SEED)
    t0, t_end, late = asyncio.run(serve.open_loop(sub, times, 0.5, SEED))
    assert len(sub.log.items) == len(times) == 100
    np.testing.assert_allclose(np.asarray(sub.log.due) - t0, times)
    assert sub.fe.peak > 1                      # sent without waiting
    assert late.max() < 0.05
    lat = np.asarray(sub.log.done) - np.asarray(sub.log.due)
    assert np.all(lat >= 0.05)


# ---------------------------------------------------------------------------
# The result line, and no result without the chip
# ---------------------------------------------------------------------------
def test_result_line_schema(cache):
    cell = "sift128-f32.paper.closed"
    rc, last, _ = run_cell(cell)
    assert rc == 0 and last is not None
    assert list(last)[:3] == ["correct", "attempted", "failed"]
    assert list(last)[-1] == "checks"
    assert last["correct"] is True and last["attempted"] > 0
    assert set(last["metrics"]) == {m["name"] for m in
                                    run.metric_specs(cell, "end_to_end")}
    for v in last["metrics"].values():
        assert set(v) == {"value", "unit"} and v["value"] > 0
    assert set(last["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for name, c in last["checks"].items():
        assert c["value"] <= c["limit"], name


def test_traced_result_line_has_program_metrics_only(cache):
    cell = "sift128-f32.paper.closed"
    rc, last, _ = run_cell(cell, trace=1)
    assert rc == 0 and last["correct"] is True
    names = set(last["metrics"])
    assert {"batch_fill.closed", "host_ms_per_batch.closed",
            "estimate_ms_per_batch", "waves_per_batch"} <= names
    # the CPU has no device plane: no device metric is reported from it
    assert not names & {"device_idle.closed", "gather_distance_roofline",
                        "graph_device_ms_per_batch"}


def test_no_tpu_no_result(cache):
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds",
                       "1", "--trace", "0"], expect_platform="tpu",
                      overrides=tiny(CELLS[0]))
    assert rc != 0 and "{" not in buf.getvalue()


def test_no_tpu_command_exits_nonzero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1", "--trace",
                        "0"], cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


# ---------------------------------------------------------------------------
# correct: the control and a broken served path come out false
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    c = workload.load_cell(cell, overrides={"pool": 256})    # cell's own N
    cfg = c.config
    vecs = workload.make_corpus(cfg)
    ints, floats = workload.make_attributes(cfg, SEED)
    pool = workload.make_pool(cfg, c.traffic, SEED)
    cols = workload.column_index(cfg)
    items = np.arange(pool.size)
    k = cfg["search"]["k"]
    ids, d = reference.control_answers(vecs, ints, floats, cols, pool, k, items)
    numbers, _ = reference.compare(items, ids, d, np.ones(len(items), bool), 0,
                                   vecs, ints, floats, cols, pool, k,
                                   brute_exact=True)
    assert not reference.judge(numbers, c.limits), numbers
    # the same scan with an exact dot product is correct
    ref = reference.exact_topk(vecs, ints, floats, cols, pool, k, items)
    numbers, diag = reference.compare(items, ref["ids"], ref["d"],
                                      np.ones(len(items), bool), 0, vecs,
                                      ints, floats, cols, pool, k,
                                      brute_exact=True)
    assert reference.judge(numbers, c.limits) and diag["recall"] == 1.0


def _tiny_inputs(cell: str, pool_size: int = 64):
    c = workload.load_cell(cell, overrides=dict(tiny(cell), pool=pool_size))
    cfg = c.config
    vecs = workload.make_corpus(cfg)
    ints, floats = workload.make_attributes(cfg, SEED)
    pool = workload.make_pool(cfg, c.traffic, SEED)
    return c, vecs, ints, floats, pool, workload.column_index(cfg)


def _compressed_limits(limits: dict) -> dict:
    """The cell's limits with a stand-in for ``brute_recall_miss``, which a
    configuration with a compressed brute route sets from its own chip
    readings: the graph route's recall limit."""
    return dict(limits, brute_recall_miss=limits["recall_miss"])


@pytest.mark.parametrize("route,brute_exact", [
    pytest.param("graph", True, id="graph"),
    pytest.param("brute", True, id="brute"),
    pytest.param("graph", False, id="graph-compressed"),
    pytest.param("brute", False, id="brute-compressed"),
])
def test_valid_rows_that_are_not_the_nearest_are_caught(route, brute_exact):
    """Rows that pass the filter, sorted, each with its true distance, but
    not the k nearest: a traversal that stops early, or a scan that keeps
    the wrong rows.  An exact scan promises the nearest rows, so they are
    bad answers; a compressed scan promises recall, so they count in
    ``brute_recall_miss`` and nowhere else."""
    cell = CELLS[0]
    c, vecs, ints, floats, pool, cols = _tiny_inputs(cell)
    limits = c.limits if brute_exact else _compressed_limits(c.limits)
    k = c.config["search"]["k"]
    items = np.arange(pool.size)
    wide = reference.exact_topk(vecs, ints, floats, cols, pool, 3 * k, items)
    assert np.all(wide["n_match"] >= 3 * k)
    ids, d = wide["ids"][:, 2 * k:], wide["d"][:, 2 * k:]  # ranks 2k..3k-1
    brute = np.full(len(items), route == "brute")
    numbers, diag = reference.compare(items, ids, d, brute, 0, vecs, ints,
                                      floats, cols, pool, k,
                                      brute_exact=brute_exact)
    assert numbers["dist_err"] < 1e-9
    assert not reference.judge(numbers, limits), numbers
    if route == "graph":
        key = "recall_miss"
    else:
        key = "bad_answers" if brute_exact else "brute_recall_miss"
        assert diag["far_brute"] == len(items)
    assert numbers[key] > limits[key]
    if route == "brute" and not brute_exact:
        assert numbers["bad_answers"] == 0 and numbers["recall_miss"] == 0.0
    # the true top-k, with ties broken either way, is correct
    numbers, _ = reference.compare(items, wide["ids"][:, :k], wide["d"][:, :k],
                                   brute, 0, vecs, ints, floats, cols, pool, k,
                                   brute_exact=brute_exact)
    assert reference.judge(numbers, limits), numbers


@pytest.mark.parametrize("fault,diag_key", [
    ("filter", "filter_fail"), ("out_of_range", "out_of_range"),
    ("duplicate", "duplicate"), ("short", "short_brute"),
    ("unsorted", "unsorted"), ("empty", "empty"),
])
def test_compressed_brute_route_keeps_every_rule_but_the_far_row(fault,
                                                                 diag_key):
    """A compressed brute route is excused its far rows only: an answer
    that fails its filter, names a row out of range or twice, stops short
    of ``min(k, rows that pass)``, is unsorted or empty is still bad."""
    c, vecs, ints, floats, pool, cols = _tiny_inputs(CELLS[0])
    k = c.config["search"]["k"]
    n = vecs.shape[0]
    items = np.arange(pool.size)
    ref = reference.exact_topk(vecs, ints, floats, cols, pool, k, items)
    ids, d = ref["ids"].copy(), ref["d"].copy()
    if fault == "filter":          # the farthest row that fails the filter
        mask = workload.eval_filter(pool.filters[pool.filter_of[0]], ints,
                                    floats, cols)
        true_d = np.linalg.norm(vecs.astype(np.float64)
                                - pool.queries[0].astype(np.float64), axis=1)
        ids[0, -1] = int(np.argmax(np.where(mask, -1.0, true_d)))
        d[0, -1] = true_d[ids[0, -1]]
    elif fault == "out_of_range":
        ids[0, -1] = n
    elif fault == "duplicate":
        ids[0, -1], d[0, -1] = ids[0, -2], d[0, -2]
    elif fault == "short":
        ids[0, -1], d[0, -1] = -1, np.inf
    elif fault == "unsorted":
        ids[0, [0, -1]], d[0, [0, -1]] = ids[0, [-1, 0]], d[0, [-1, 0]]
    else:
        ids[0], d[0] = -1, np.inf
    numbers, diag = reference.compare(items, ids, d, np.ones(len(items), bool),
                                      0, vecs, ints, floats, cols, pool, k,
                                      brute_exact=False)
    assert diag[diag_key] == 1, diag
    assert numbers["bad_answers"] == 1, numbers
    assert not reference.judge(numbers, _compressed_limits(c.limits))


@pytest.mark.parametrize("use_pq", [False, True])
def test_checks_follow_the_brute_routes_promise(use_pq):
    """An exact brute route keeps the four checks and their values; a
    compressed one adds ``brute_recall_miss``, which its limits must give."""
    c, vecs, ints, floats, pool, cols = _tiny_inputs(CELLS[0])
    k = c.config["search"]["k"]
    items = np.arange(pool.size)
    ref = reference.exact_topk(vecs, ints, floats, cols, pool, k, items)
    brute = np.arange(pool.size) % 2 == 0       # both routes answer
    args = (items, ref["ids"], ref["d"], brute, 0, vecs, ints, floats, cols,
            pool, k)
    exact, _ = reference.compare(*args, brute_exact=True)
    assert list(exact) == ["unanswered", "bad_answers", "recall_miss",
                           "dist_err"]
    numbers, _ = reference.compare(*args, brute_exact=not use_pq)
    if not use_pq:
        assert numbers == exact
        return
    assert {n: numbers[n] for n in exact} == exact
    assert numbers["brute_recall_miss"] == 0.0
    with pytest.raises(KeyError, match="brute_recall_miss"):
        reference.judge(numbers, c.limits)
    assert reference.judge(numbers, _compressed_limits(c.limits))


def _break(monkeypatch, route: str, fault: str):
    """Break the served path underneath the front end: the backend's
    ``route`` search returns altered answers."""
    import jax.numpy as jnp
    from repro.core.backend import LocalBackend
    name = "search_graph" if route == "graph" else "search_brute"
    orig = getattr(LocalBackend, name)
    last = {}

    def broken(self, *a, **kw):
        out = orig(self, *a, **kw)
        ids, dists = (out["ids"], out["dists"]) if route == "graph" else out
        ids, dists = np.array(ids), np.array(dists)
        if fault == "altered":             # one answer of the batch altered
            ids[0] = (ids[0] + 1) % self.index.index.n
        elif fault == "half":              # half of the batch left out
            ids[len(ids) // 2:] = -1
            dists[len(ids) // 2:] = np.inf
        elif fault == "stale":             # the previous batch's answers
            prev = last.get("out")
            last["out"] = (ids.copy(), dists.copy())
            if prev is not None:
                ids, dists = prev
        ids, dists = jnp.asarray(ids), jnp.asarray(dists)
        if route == "graph":
            return dict(out, ids=ids, dists=dists)
        return ids, dists

    monkeypatch.setattr(LocalBackend, name, broken)


@pytest.mark.parametrize("cell,route,fault", [
    ("sift128-f32.paper.closed", "graph", "altered"),
    ("sift128-f32.paper.closed", "graph", "half"),
    ("sift128-f32.paper.closed", "graph", "stale"),
])
def test_broken_path_is_not_correct(cache, monkeypatch, cell, route, fault):
    _break(monkeypatch, route, fault)
    rc, last, out = run_cell(cell)
    assert rc == 0 and last is not None, out
    assert last["correct"] is False, last["checks"]


def _pq(monkeypatch, cell: str) -> dict:
    """Overrides that give a tiny cell the compressed brute route of a PQ
    deployment (32 subspaces of 8 bits, as SIFT's PQ papers use) and send
    every request down it, with a stand-in limit for its recall."""
    import functools
    import repro.core
    monkeypatch.setattr(repro.core, "SearchOptions", functools.partial(
        repro.core.SearchOptions, force="brute"))
    load = workload.load_cell

    def load_cell(name, root=workload.ROOT, overrides=None):
        c = load(name, root, overrides)
        return workload.Cell(c.name, c.config, c.traffic, c.chips,
                             _compressed_limits(c.limits))

    monkeypatch.setattr(workload, "load_cell", load_cell)
    ov = tiny(cell)
    ov["quant"] = {"kind": "pq", "m": 32, "nbits": 8, "train_iters": 2}
    ov["search"] = dict(ov["search"], use_pq=True)
    return ov


def test_compressed_brute_route_is_judged_by_its_recall(cache, monkeypatch):
    """Through ``run.main``: the PQ scan with its configured re-rank is
    correct; with ``search.rerank = 0`` (``control.py --fault rerank_k``)
    its far rows cost recall, and ``brute_recall_miss`` catches them while
    ``bad_answers`` stays 0."""
    import control
    ov = _pq(monkeypatch, CELLS[0])
    cell = workload.load_cell(CELLS[0])
    sound, diag = control.served_readings(cell, SEED, 1.0, {}, "cpu",
                                          overrides=ov)
    assert diag["attempted"] > 0
    assert reference.judge(sound, cell.limits), sound
    fault, _ = control.rerank_k_readings(cell, SEED, 1.0, "cpu", overrides=ov)
    assert fault["bad_answers"] == 0 and fault["recall_miss"] == 0.0
    assert fault["dist_err"] <= cell.limits["dist_err"]
    assert fault["brute_recall_miss"] > cell.limits["brute_recall_miss"], fault
    assert fault["brute_recall_miss"] > 3 * sound["brute_recall_miss"]


def test_rerank_k_needs_a_compressed_brute_route():
    import control
    with pytest.raises(SystemExit, match="no compressed brute route"):
        control.rerank_k_readings(workload.load_cell(CELLS[0]), SEED, 1.0,
                                  "cpu", overrides=tiny(CELLS[0]))


def test_traversal_that_ignores_ef_is_not_correct(cache):
    """The graph route run with its search width cut to k (``control.py
    --fault ef_k``, the planted traversal fault): its rows carry their true
    distances, and ``recall_miss`` catches it."""
    import control
    cell = workload.load_cell(CELLS[0])
    numbers, diag = control.ef_k_readings(cell, SEED, 2.0, "cpu",
                                          overrides=tiny(CELLS[0]))
    assert diag["attempted"] > 0
    assert numbers["dist_err"] <= cell.limits["dist_err"]
    assert numbers["recall_miss"] > cell.limits["recall_miss"], numbers


# ---------------------------------------------------------------------------
# Trace reduction and the roofline counts
# ---------------------------------------------------------------------------
def _ev(name, start, dur):
    return SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def _trace():
    line = lambda name, evs: SimpleNamespace(name=name, events=evs)  # noqa: E731
    host = SimpleNamespace(name="/host:CPU", lines=[line("python3", [
        _ev("bench.window", 1000, 10000),
        _ev("favor/graph/b256", 1500, 3000),
        _ev("PjitFunction(estimate_batched)", 6500, 2500)])])
    ops = [
        _ev("%fusion.1 = f32[8] fusion(f32[8] %p)", 500, 1000),   # clipped to 1000-1500
        _ev("%while.2 = (s32[]) while((s32[]) %t)", 2000, 3000),  # control flow
        _ev("%custom-call.3 = f32[256,32] custom-call(), "
            "custom_call_target=\"tpu_custom_call\", name=favor.gather_distance",
            2000, 2000),
        _ev("%fusion.4 = pred[8] fusion(u32[8] %q)", 3000, 2000),  # overlaps 3000-4000
        _ev("%copy.5 = f32[8] copy(f32[8] %x)", 10500, 1000),     # clipped to 11000
    ]
    mods = [_ev("jit_favor_graph_search(123)", 500, 4500),
            _ev("jit_estimate_batched(456)", 10400, 2000)]
    dev = SimpleNamespace(name="/device:TPU:0",
                          lines=[line("XLA Modules", mods), line("XLA Ops", ops)])
    return SimpleNamespace(planes=[host, dev])


def test_trace_reduction():
    r = trace_reduce.reduce_profile(_trace())
    assert r["window_s"] == pytest.approx(10000e-9)
    # busy: 1000-1500, 2000-5000, 10500-11000
    assert r["busy_s"] == pytest.approx(4000e-9)
    assert r["kernel_s"] == {"gather_distance": pytest.approx(2000e-9)}
    assert r["module_s"]["jit_favor_graph_search"] == pytest.approx(4000e-9)
    assert r["module_s"]["jit_estimate_batched"] == pytest.approx(600e-9)
    assert r["module_calls"] == {"jit_favor_graph_search": 1,
                                 "jit_estimate_batched": 1}
    top = dict(r["top_ops"])
    assert top["jit_favor_graph_search:favor.gather_distance"] == pytest.approx(2000e-9)
    assert top["jit_favor_graph_search:fusion"] == pytest.approx(2500e-9)
    assert not [k for k in top if k.endswith(":while")]
    gaps = dict(r["idle_gaps"])
    # idle 1500-2000 (inside favor/graph/b256), 5000-10500 (middle 7750:
    # inside PjitFunction(estimate_batched))
    assert gaps["favor/graph/b256"] == pytest.approx(500e-9)
    assert gaps["PjitFunction(estimate_batched)"] == pytest.approx(5500e-9)


def test_window_registry_is_the_difference():
    before = {"counters": {"c": {"series": {"": 5.0}}},
              "histograms": {"h": {"series": {'stage="x"': {"sum": 1.0, "count": 2}}}},
              "views": {"frontend": {"coalesce": {"dispatches": 3, "rows": 700}}}}
    after = {"counters": {"c": {"series": {"": 12.0}}},
             "histograms": {"h": {"series": {'stage="x"': {"sum": 4.0, "count": 5}}}},
             "views": {"frontend": {"coalesce": {"dispatches": 7, "rows": 1724}}}}
    w = layer.window_registry(before, after)
    assert w["counters"]["c"]["series"][""] == 7.0
    assert w["histograms"]["h"]["series"]['stage="x"'] == {"sum": 3.0, "count": 3}
    assert w["views"]["frontend"]["coalesce"] == {"dispatches": 4, "rows": 1024}


def test_host_ms_per_batch_leaves_out_each_routes_dispatch():
    """The ``graph`` and ``brute`` spans less their ``search`` children,
    which enqueue the device work, with the other host stages."""
    stages = {"compile": 0.010, "cache_lookup": 0.001, "route": 0.002,
              "graph": 0.050, "graph/pad": 0.003, "graph/search": 0.040,
              "brute": 0.020, "brute/search": 0.015, "estimate": 0.5}
    reg = {"counters": {"favor_batches_total": {"series": {"": 10.0}}},
           "histograms": {"favor_stage_seconds": {"series": {
               f'stage="{k}"': {"sum": v, "count": 10}
               for k, v in stages.items()}}},
           "views": {}}
    c = workload.load_cell(CELLS[0])
    ctx = layer.Context(c.config, c.traffic, reg, None, "TPU v5 lite")
    host = 0.010 + 0.001 + 0.002 + (0.050 - 0.040) + (0.020 - 0.015)
    assert run.load_reader("host_ms_per_batch.closed")(ctx) == \
        pytest.approx(1e3 * host / 10)


def _ctx(cell, counters=None, kernels=None, calls=None):
    c = workload.load_cell(cell)
    reg = {"counters": {k: {"series": {"": v}} for k, v in (counters or {}).items()},
           "histograms": {}, "views": {}}
    tr = {"kernel_s": kernels or {}, "module_calls": calls or {},
          "module_s": {}, "busy_s": 1.0, "window_s": 2.0}
    return layer.Context(c.config, c.traffic, reg, tr, "TPU v5 lite")


def test_gather_distance_roofline_hand_count():
    ctx = _ctx("sift128-f32.paper.closed", {"favor_graph_hops_total": 1000.0},
               {"gather_distance": 1e-3})
    # 1000 hops x 32 neighbours; each row 128 f32 + norm + 3 attrs + id
    nbytes = 1000 * 32 * (512 + 4 + 12 + 4)
    ops = 1000 * 32 * 2 * 128
    want = 100 * max(ops / 197e12, nbytes / 819e9) / 1e-3
    assert run.load_reader("gather_distance_roofline")(ctx) == pytest.approx(want)
    assert "bound=bytes" in ctx.notes[0]


def test_unknown_device_is_an_error():
    ctx = _ctx("sift128-f32.paper.closed", {"favor_graph_hops_total": 10.0},
               {"gather_distance": 1e-3})
    ctx.device_kind = "TPU v99"
    with pytest.raises(KeyError):
        run.load_reader("gather_distance_roofline")(ctx)


def test_index_cache_key_covers_src(tmp_path):
    cfg = workload.load_cell(CELLS[0]).config
    vecs = np.ones((4, 2), np.float32)
    (tmp_path / "src").mkdir()
    (tmp_path / "src" / "a.py").write_text("x = 1\n")
    k1 = index_cache.cache_key(cfg, vecs, tmp_path)
    assert index_cache.cache_key(cfg, vecs, tmp_path) == k1
    assert index_cache.cache_key(cfg, vecs + 1, tmp_path) != k1
    assert index_cache.cache_key(dict(cfg, hnsw={"M": 8}), vecs, tmp_path) != k1
    (tmp_path / "src" / "a.py").write_text("x = 2\n")
    assert index_cache.cache_key(cfg, vecs, tmp_path) != k1
    assert math.isfinite(len(k1))


def test_cached_graph_serves_each_seeds_attributes(tmp_path):
    """The first run builds the graph; a later run of another seed loads it
    and puts its own attributes beside it."""
    cfg = workload.load_cell(CELLS[0], overrides=tiny(CELLS[0])).config
    vecs = workload.make_corpus(cfg)
    a1 = workload.make_attributes(cfg, SEED)
    a2 = workload.make_attributes(cfg, SEED + 1)
    fi1, build_s = index_cache.get_index(cfg, vecs, *a1, cache=tmp_path)
    fi2, again = index_cache.get_index(cfg, vecs, *a2, cache=tmp_path)
    assert build_s > 0 and again is None
    assert np.array_equal(fi2.attrs.ints, a2[0])
    assert np.array_equal(fi2.attrs.floats, a2[1])
    assert not np.array_equal(fi1.attrs.ints, fi2.attrs.ints)
    assert fi2.index.entry_point == fi1.index.entry_point
    for lv1, lv2 in zip(fi1.index.levels, fi2.index.levels):
        assert np.array_equal(lv1, lv2)
    assert np.array_equal(fi2.index.vectors, vecs)
