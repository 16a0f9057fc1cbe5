"""Tests of the rare-filter PQ cell (``sift128-pq.rare.closed``) and its
readers, on the CPU at a tiny size.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They check that the cell's files load with the compressed route's fifth
limit, that its traffic stays under the selector's threshold, that a tiny
run of it is correct with only filters at or above it on the graph, and the
operation and byte counts of ``pq_adc_topr_roofline``.  No number here is a
device measurement.
"""
from __future__ import annotations

import ast
import dataclasses
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src"), str(Path(__file__).parent)]

import layer  # noqa: E402
import run  # noqa: E402
import workload  # noqa: E402
from test_bench import SEED, cache, run_cell, tiny  # noqa: E402,F401

CELL = "sift128-pq.rare.closed"
NEW_METRICS = ("brute_device_ms_per_batch", "pq_adc_topr_roofline",
               "brute_outside_scan_ms_per_batch",
               "estimate_check_ms_per_batch", "graph_share.rare")


@pytest.fixture
def one_device(monkeypatch):
    """The cell holds a four-chip host for the steadiness of its host-bound
    rate; its tiny CPU run is the same program on the one CPU device."""
    load = workload.load_cell
    monkeypatch.setattr(workload, "load_cell", lambda name, **kw: dataclasses
                        .replace(load(name, **kw), chips=1))


def test_rare_cell_files_load():
    c = workload.load_cell(CELL)
    assert c.chips == 4
    assert set(c.limits) == {"unanswered", "bad_answers", "recall_miss",
                             "dist_err", "brute_recall_miss"}
    assert c.config["quant"] == {"kind": "pq", "m": 32, "nbits": 8}
    assert c.config["search"]["use_pq"] and c.config["search"]["use_pallas"]
    assert c.config["corpus"] == workload.load_cell(
        "sift128-f32.paper.closed").config["corpus"]
    assert {m["name"] for m in run.metric_specs(CELL, "per_layer")} == \
        set(NEW_METRICS)


@pytest.mark.parametrize("seed", [SEED, 2**31 + 54321])
def test_rare_filters_stay_under_lambda(seed):
    """Over the cell's own N, every pool filter passes some rows and fewer
    than 1% of them: the selector's threshold."""
    c = workload.load_cell(CELL)
    ints, floats = workload.make_attributes(c.config, seed)
    pool = workload.make_pool(c.config, c.traffic, seed)
    cols = workload.column_index(c.config)
    sel = np.asarray([workload.eval_filter(f, ints, floats, cols).mean()
                      for f in pool.filters])
    assert len(sel) == c.traffic["pool"]
    assert sel.max() < 0.01 and sel.min() > 0.0
    assert 0.003 < np.median(sel) < 0.006


def test_rare_cell_is_correct_and_graph_only_at_or_above_lambda(cache,
                                                                one_device):
    """A tiny traced run is correct, with ``brute_recall_miss`` checked.  At
    the tiny N a few pool filters pass 1% of the rows or more; only those
    may take the graph route (the selector's check holds every other one on
    the brute route)."""
    rc, last, out = run_cell(CELL, trace=1)
    assert rc == 0 and last is not None, out
    assert last["correct"] is True, last["checks"]
    assert "brute_recall_miss" in last["checks"]
    c = workload.load_cell(CELL, overrides=tiny(CELL))
    ints, floats = workload.make_attributes(c.config, SEED)
    pool = workload.make_pool(c.config, c.traffic, SEED)
    cols = workload.column_index(c.config)
    sel = np.asarray([workload.eval_filter(f, ints, floats, cols).mean()
                      for f in pool.filters])
    warm = [ln for ln in out.splitlines() if ln.startswith("warm-up: ")]
    seen = ast.literal_eval(warm[0][len("warm-up: "):warm[0].index("}") + 1])
    assert seen["pool_graph"] <= int((sel >= 0.01).sum()) < 4
    assert 0.0 <= last["metrics"]["graph_share.rare"]["value"] < 10.0
    # the CPU has no device plane: no device metric is reported from it
    assert not set(last["metrics"]) & {"brute_device_ms_per_batch",
                                       "pq_adc_topr_roofline",
                                       "brute_outside_scan_ms_per_batch"}


def _ctx(counters=None, kernels=None, modules=None, calls=None, hists=None):
    c = workload.load_cell(CELL)
    reg = {"counters": {name: {"series": series}
                        for name, series in (counters or {}).items()},
           "histograms": {name: {"series": series}
                          for name, series in (hists or {}).items()},
           "views": {}}
    tr = {"kernel_s": kernels or {}, "module_calls": calls or {},
          "module_s": modules or {}, "busy_s": 1.0, "window_s": 2.0}
    return layer.Context(c.config, c.traffic, reg, tr, "TPU v5 lite")


def test_pq_adc_topr_roofline_counts():
    """ops 2 b N m; bytes c N (m + 4 + 12) + b (m K 4 + 8 R), R = 40."""
    read = run.load_reader("pq_adc_topr_roofline")
    mod = SimpleNamespace(**read.__globals__)
    b, c, n, m, ksub, r = 1000.0, 4, 32768, 32, 256, 40
    assert mod.candidates(workload.load_cell(CELL).config) == r
    ops = mod.scan_ops(b, n, m)
    nbytes = mod.scan_bytes(c, b, n, m, ksub, r, 12)
    assert ops == 2 * 1000 * 32768 * 32
    assert nbytes == 4 * 32768 * (32 + 4 + 12) + 1000 * (32 * 256 * 4 + 320)
    ctx = _ctx(counters={"favor_requests_total": {'route="brute"': b,
                                                  'route="graph"': 7.0}},
               kernels={"pq_adc_topr": 0.01},
               calls={"jit_pq_prefbf_topk": c})
    pk = ctx.peaks()
    least = max(ops / pk["flops_bf16"], nbytes / pk["hbm_bytes_per_s"])
    assert read(ctx) == pytest.approx(100.0 * least / 0.01)
    assert "bound=bytes" in ctx.notes[-1]


def test_brute_readers_split_the_program():
    ctx = _ctx(modules={"jit_pq_prefbf_topk": 0.030},
               kernels={"pq_adc_topr": 0.012},
               counters={"favor_batches_total": {"": 10.0}})
    assert run.load_reader("brute_device_ms_per_batch")(ctx) == \
        pytest.approx(3.0)
    assert run.load_reader("brute_outside_scan_ms_per_batch")(ctx) == \
        pytest.approx(1.8)


def test_check_and_share_readers():
    ctx = _ctx(counters={"favor_batches_total": {"": 4.0},
                         "favor_requests_total": {'route="brute"': 1000.0,
                                                  'route="graph"': 24.0}},
               hists={"favor_stage_seconds": {
                   'stage="estimate/check"': {"sum": 0.008, "count": 3}}})
    assert run.load_reader("estimate_check_ms_per_batch")(ctx) == \
        pytest.approx(2.0)
    assert run.load_reader("graph_share.rare")(ctx) == \
        pytest.approx(100.0 * 24 / 1024)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_readers_find_nothing_without_it(name):
    """A program without the check, and a window without brute work or a
    device trace, give no reading, and no reader raises."""
    assert run.load_reader(name)(_ctx()) is None
