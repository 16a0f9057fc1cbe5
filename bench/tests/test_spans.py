"""Tests of ``spans``: the traversal's device time by wave stage and the
device's idle time by program span, on small captures built here.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_spans.py

A capture is an XSpace written as a text proto and read back through
``jax.profiler.ProfileData``, shaped as a TPU capture is: each operation's
name stack in the ``tf_op`` stat of its event metadata.  No number here is
a device measurement.
"""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import layer  # noqa: E402
import run  # noqa: E402
import spans  # noqa: E402
import trace_reduce  # noqa: E402
import workload  # noqa: E402

CELL = "sift128-f32.paper.closed"
PROG = "jit(favor_graph_search)"


def capture(*, window=True, span_prefix="favor.", scopes=True) -> str:
    """A text-proto XSpace: one host plane with the window and program
    spans on two threads, one TPU plane.  Times in ns:

    device ops   [1000, 2500] [4000, 4600] [5000, 5600] [9800, 9900]
    spans        estimate/wait [1000, 3000], graph/search [2500, 3500]
                 (another thread), fetch [6000, 8000]
    window       [0, 10000]

    ``scopes=False`` and ``span_prefix="favor/"`` make the capture of a
    program without the stage scopes and with the older annotation names.
    """
    p = span_prefix
    win = ('events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }'
           if window else "")
    sel, merge, init = (("wave.select/", "wave.merge/", "graph.init/")
                        if scopes else ("", "", ""))
    return f'''
planes {{
  id: 1 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
    {win}
    events {{ metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000
             stats {{ metadata_id: 1 int64_value: 7 }} }}
    events {{ metadata_id: 4 offset_ps: 6000000 duration_ps: 2000000 }}
  }}
  lines {{ id: 2 name: "favor-step_1" timestamp_ns: 0
    events {{ metadata_id: 3 offset_ps: 2500000 duration_ps: 1000000 }}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.window" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "{p}estimate/wait" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "{p}graph/search" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "{p}fetch" }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "trace_id" }} }}
}}
planes {{
  id: 2 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 500000 duration_ps: 9000000 }}
  }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
    events {{ metadata_id: 2 offset_ps: 1000000 duration_ps: 1000000 }}
    events {{ metadata_id: 3 offset_ps: 2000000 duration_ps: 500000 }}
    events {{ metadata_id: 4 offset_ps: 4000000 duration_ps: 400000 }}
    events {{ metadata_id: 5 offset_ps: 4400000 duration_ps: 200000 }}
    events {{ metadata_id: 6 offset_ps: 5000000 duration_ps: 600000 }}
    events {{ metadata_id: 7 offset_ps: 9800000 duration_ps: 100000 }}
  }}
  event_metadata {{ key: 1 value {{ id: 1
      name: "jit_favor_graph_search(42)" }} }}
  event_metadata {{ key: 2 value {{ id: 2
      name: "%fusion.12 = s32[256] fusion(f32[256,128] %p), kind=kLoop"
      stats {{ metadata_id: 1 str_value: "{PROG}/while/body/{sel}argmin:" }}
      stats {{ metadata_id: 2 str_value: "loop fusion" }} }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "%sort.77 = f32[8] sort()"
      stats {{ metadata_id: 1
               str_value: "{PROG}/while/body/{merge}jit(argsort)/sort:" }} }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "%dot.3 = f32[8] dot()"
      stats {{ metadata_id: 1 str_value: "{PROG}/{init}jit(gather_distance)/favor.gather_distance/dot_general:" }} }} }}
  event_metadata {{ key: 5 value {{ id: 5 name: "%while.1 = (s32[]) while()"
      stats {{ metadata_id: 1 str_value: "{PROG}/{sel}while:" }} }} }}
  event_metadata {{ key: 6 value {{ id: 6 name: "%copy.2 = f32[8] copy()"
      stats {{ metadata_id: 1 str_value: "{PROG}/while/body/add:" }} }} }}
  event_metadata {{ key: 7 value {{ id: 7 name: "%fusion.9 = f32[8] fusion()" }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }}
  stat_metadata {{ key: 2 value {{ id: 2 name: "hlo_category" }} }}
}}
'''


def write(tmp_path: Path, text: str) -> str:
    from jax.profiler import ProfileData
    d = tmp_path / "trace" / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    return str(tmp_path / "trace")


def ctx_with(batches: int) -> layer.Context:
    c = workload.load_cell(CELL)
    reg = {"counters": {"favor_batches_total": {"series": {"": batches}}},
           "histograms": {}, "views": {}}
    return layer.Context(c.config, c.traffic, reg, None, "TPU v5 lite")


def test_innermost_scope():
    assert spans.innermost_scope(
        "jit(favor_graph_search)/while/body/wave.score/jit(gather_distance)/"
        "favor.gather_distance/dot_general") == "wave.score"
    assert spans.innermost_scope("jit(f)/graph.init/while/body/add") == \
        "graph.init"
    assert spans.innermost_scope("jit(f)/while/body/add") is None
    assert spans.innermost_scope("jit(f)/wave.selected_x/add") is None
    assert spans.innermost_scope(f"{PROG}/while/body/wave.merge:") == \
        "wave.merge"
    # no stage name can pass for a kernel in trace_reduce
    for scope in spans.STAGES:
        assert not trace_reduce.KERNEL_RE.search(scope)


def test_plane_metadata_reads_the_tf_op_stat():
    from jax.profiler import ProfileData
    meta = spans.plane_metadata(
        ProfileData.text_proto_to_serialized_xspace(capture()))
    ops = meta["/device:TPU:0"]
    name = "%fusion.12 = s32[256] fusion(f32[256,128] %p), kind=kLoop"
    assert ops[name] == {"tf_op": f"{PROG}/while/body/wave.select/argmin:",
                         "hlo_category": "loop fusion"}
    assert "%fusion.9 = f32[8] fusion()" in ops     # no stats: empty
    assert meta["/host:CPU"]["favor.fetch"] == {}


def test_stage_and_idle_attribution(tmp_path):
    r = spans.load(write(tmp_path, capture()))
    assert r["window_s"] == pytest.approx(10000e-9)
    # each op to the innermost stage of its tf_op (graph.init above the
    # kernel's own favor.* scope); the while op is control flow; the copy is
    # the traversal's own but unscoped; the last op ran outside the program
    assert r["stage_s"] == {"wave.select": pytest.approx(1000e-9),
                            "wave.merge": pytest.approx(500e-9),
                            "graph.init": pytest.approx(400e-9),
                            "unscoped": pytest.approx(600e-9)}
    # idle: [0,1000] [2500,4000] [4600,5000] [5600,9800] [9900,10000]
    assert r["idle_s"] == pytest.approx(7200e-9)
    by = r["idle_by_span"]
    # [2500,3000] has both spans open: the shorter, graph/search, is blamed
    assert by == {"unspanned": pytest.approx(4200e-9),
                  "fetch": pytest.approx(2000e-9),
                  "graph/search": pytest.approx(1000e-9)}
    assert sum(by.values()) == pytest.approx(r["idle_s"])


def test_readers_per_batch_and_share(tmp_path, monkeypatch):
    trace_dir = write(tmp_path, capture())
    monkeypatch.setattr(run, "TRACE_DIR", Path(trace_dir))
    ctx = ctx_with(batches=2)
    assert run.load_reader("wave_select_ms_per_batch")(ctx) == \
        pytest.approx(1e3 * 1000e-9 / 2)
    assert run.load_reader("wave_merge_ms_per_batch")(ctx) == \
        pytest.approx(1e3 * 500e-9 / 2)
    assert run.load_reader("graph_init_ms_per_batch")(ctx) == \
        pytest.approx(1e3 * 400e-9 / 2)
    assert run.load_reader("wave_score_ms_per_batch")(ctx) == 0.0
    assert run.load_reader("idle_unspanned.closed")(ctx) == \
        pytest.approx(100.0 * 4200 / 7200)
    assert any(n.startswith("wave stages s:") for n in ctx.notes)
    assert any(n.startswith("idle s by span") for n in ctx.notes)


def test_a_program_without_scopes_or_spans_reads_nothing(tmp_path,
                                                         monkeypatch):
    """The parent's program: no graph/wave scope, host annotations named
    ``favor/...``: every new device reader returns None, none raises."""
    trace_dir = write(tmp_path, capture(span_prefix="favor/", scopes=False))
    monkeypatch.setattr(run, "TRACE_DIR", Path(trace_dir))
    r = spans.load(trace_dir)
    assert r["stage_s"] is None and r["idle_by_span"] is None
    ctx = ctx_with(batches=2)
    for name in ("graph_init_ms_per_batch", "wave_select_ms_per_batch",
                 "wave_visit_ms_per_batch", "wave_score_ms_per_batch",
                 "wave_filter_ms_per_batch", "wave_merge_ms_per_batch",
                 "idle_unspanned.closed"):
        assert run.load_reader(name)(ctx) is None, name


def test_no_window_or_no_capture_reads_nothing(tmp_path):
    assert spans.load(str(tmp_path / "none")) is None
    assert spans.load(write(tmp_path, capture(window=False))) is None
