"""Tests of the ``filter_lower_ms_per_batch`` reader, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

The reader reads the router's ``compile/lower`` span: nothing on a window
without it, the per-batch mean otherwise.  No number here is a device
measurement.
"""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import layer  # noqa: E402
import run  # noqa: E402
import workload  # noqa: E402

METRIC = "filter_lower_ms_per_batch"
CELLS = ("sift128-f32.paper.closed", "sift128-pq.rare.closed")


def _ctx(cell, batches=0.0, stages=None):
    c = workload.load_cell(cell)
    reg = {"counters": {"favor_batches_total": {"series": {"": batches}}},
           "histograms": {"favor_stage_seconds": {"series": {
               f'stage="{k}"': {"sum": s, "count": n}
               for k, (s, n) in (stages or {}).items()}}},
           "views": {}}
    return layer.Context(c.config, c.traffic, reg, None, "TPU v5 lite")


@pytest.mark.parametrize("cell", CELLS)
def test_filter_lower_reported_in_both_cells(cell):
    assert METRIC in {m["name"] for m in run.metric_specs(cell, "per_layer")}


@pytest.mark.parametrize("cell", CELLS)
def test_filter_lower_reads_nothing_without_the_span(cell):
    """The parent program opens ``compile`` but no ``compile/lower``."""
    read = run.load_reader(METRIC)
    assert read(_ctx(cell)) is None
    assert read(_ctx(cell, 10.0, {"compile": (0.2, 10)})) is None


@pytest.mark.parametrize("cell", CELLS)
def test_filter_lower_reads_the_per_batch_mean(cell):
    ctx = _ctx(cell, 8.0, {"compile": (0.05, 8),
                           "compile/lower": (0.016, 8),
                           "compile/upload": (0.004, 8)})
    assert run.load_reader(METRIC)(ctx) == pytest.approx(2.0)
