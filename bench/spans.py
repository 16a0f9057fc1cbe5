"""The traversal's device time by wave stage, and the device's idle time by
program span, from one traced run's profiler capture.

``load`` parses the window's capture once per run (``run.TRACE_DIR``) and
returns:

* ``stage_s`` -- device seconds of the operations inside the window, each
  given to the innermost ``graph.*`` / ``wave.*`` scope of its name stack
  (``core/search.py`` opens them at the traversal's seams).  The name stack
  is the op's ``op_name`` metadata, which a TPU capture keeps in the
  ``tf_op`` stat of the operation's event metadata.  A fusion carries the
  ``op_name`` of its root, so a fusion is given to its root's stage.
  Operations of the traversal program that no such scope covers go under
  ``unscoped``; control flow (``while``, ``conditional``, ``call``) is left
  out, as in ``trace_reduce``, so nothing counts twice;
* ``idle_s`` -- seconds inside the window with no operation on the first
  device (``trace_reduce``'s gaps);
* ``idle_by_span`` -- those seconds by the program span open at the time:
  the innermost (shortest) ``favor.*`` host annotation open on any thread,
  or ``unspanned`` where none was.  The program opens one annotation per
  span (``repro.obs.trace``).

A capture with no such scope gives no ``stage_s``, and one with no
``favor.*`` annotation gives no ``idle_by_span``: the readers then return
None.
"""
from __future__ import annotations

import bisect
import os
import re
from collections import defaultdict

import numpy as np

import trace_reduce

STAGES = ("graph.init", "wave.select", "wave.visit", "wave.score",
          "wave.filter", "wave.merge", "graph.compact")
SCOPE_RE = re.compile(r"(?:^|/)(%s)(?=[/:]|$)" % "|".join(
    re.escape(st) for st in STAGES))
NAME_STACK_STAT = "tf_op"
SPAN_PREFIX = "favor."
UNSPANNED = "unspanned"
UNSCOPED = "unscoped"
PROGRAM = "jit_favor_graph_search"

_cache: dict = {}


def innermost_scope(name_stack: str) -> str | None:
    """The last of the traversal's stage scopes in an op's name stack."""
    found = SCOPE_RE.findall(name_stack or "")
    return found[-1] if found else None


# -- the operations' metadata -------------------------------------------------
# ``ProfileData`` gives an event's own stats but not those of its metadata,
# where a TPU operation keeps its ``tf_op``.  This reads the planes'
# metadata maps from the XSpace protobuf (xplane.proto field numbers),
# skipping the events.

def _varint(buf: bytes, i: int) -> tuple[int, int]:
    shift = out = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, lo: int = 0, hi: int | None = None):
    """(field number, value) of one message; length-delimited values come
    as (start, end) offsets into ``buf``."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 1:
            val, i = None, i + 8
        elif wt == 2:
            n, i = _varint(buf, i)
            val, i = (i, i + n), i + n
        elif wt == 5:
            val, i = None, i + 4
        else:
            raise ValueError(f"xplane: wire type {wt} at byte {i}")
        yield num, val


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _entries(buf: bytes, span):
    """The values of a protobuf map field's entry (key = 1, value = 2)."""
    return [v for n, v in _fields(buf, *span) if n == 2]


def plane_metadata(buf: bytes) -> dict:
    """Plane name -> {event metadata name: {stat name: str}}, for the
    string-valued (or string-ref) stats of every plane's event metadata."""
    out = {}
    for num, plane in _fields(buf):
        if num != 1:                          # XSpace.planes
            continue
        name, stat_names, events = "", {}, []
        for pn, pv in _fields(buf, *plane):
            if pn == 2:                       # XPlane.name
                name = _text(buf, pv)
            elif pn == 5:                     # XPlane.stat_metadata
                for sm in _entries(buf, pv):
                    f = dict(_fields(buf, *sm))
                    stat_names[f.get(1, 0)] = _text(buf, f.get(2, (0, 0)))
            elif pn == 4:                     # XPlane.event_metadata
                for em in _entries(buf, pv):
                    f = list(_fields(buf, *em))
                    ename = next((_text(buf, v) for n, v in f if n == 2), "")
                    stats = [dict(_fields(buf, *v)) for n, v in f if n == 5]
                    events.append((ename, stats))
        meta = {}
        for ename, stats in events:
            d = {}
            for st in stats:                  # XStat
                key = stat_names.get(st.get(1, 0), "")
                if 5 in st:                   # str_value
                    d[key] = _text(buf, st[5])
                elif 7 in st:                 # ref_value: a stat name
                    d[key] = stat_names.get(st[7], "")
            meta.setdefault(ename, d)
        out[name] = meta
    return out


# -- the reduction ----------------------------------------------------------

def _idle_fn(gaps: np.ndarray):
    """F(t): idle time in [window start, t], for disjoint sorted gaps."""
    g0, ln = gaps[:, 0], gaps[:, 1] - gaps[:, 0]
    cum = np.concatenate([[0.0], np.cumsum(ln)[:-1]])

    def f(t):
        j = np.searchsorted(g0, t, side="right") - 1
        jj = np.maximum(j, 0)
        return np.where(j >= 0, cum[jj] + np.clip(t - g0[jj], 0.0, ln[jj]),
                        0.0)
    return f


def reduce_profile(pd, meta: dict | None = None,
                   window_name: str = trace_reduce.WINDOW) -> dict | None:
    """Stage seconds and idle seconds by span of one capture (module
    docstring); ``meta`` is ``plane_metadata`` of the same capture.  None
    when the capture has no window or no device operation in it."""
    meta = meta or {}
    win, spans = None, []
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for ln in plane.lines:
            for ev in ln.events:
                s, d = float(ev.start_ns), float(ev.duration_ns)
                if ev.name == window_name:
                    win = (s, s + d)
                elif ev.name.startswith(SPAN_PREFIX) and d > 0:
                    spans.append((s, s + d, ev.name[len(SPAN_PREFIX):]))
    devices = [p for p in pd.planes if p.name.startswith("/device:TPU")]
    if win is None or not devices:
        return None
    lo, hi = win
    stage_s = defaultdict(float)
    scoped = False
    first_ops = None
    for plane in devices:
        tf_ops = meta.get(plane.name, {})
        scopes = {}         # one lookup per distinct operation
        ml = trace_reduce._line(plane, "XLA Modules")
        mods = sorted((float(ev.start_ns),
                       float(ev.start_ns) + float(ev.duration_ns),
                       trace_reduce._module(ev.name))
                      for ev in (ml.events if ml is not None else ()))
        starts = [m[0] for m in mods]
        ivs = []
        ol = trace_reduce._line(plane, "XLA Ops")
        for ev in (ol.events if ol is not None else ()):
            s, d = float(ev.start_ns), float(ev.duration_ns)
            if s + d <= lo or s >= hi or d <= 0:
                continue
            s, e = max(s, lo), min(s + d, hi)
            ivs.append((s, e))
            kind = trace_reduce.OP_KIND_RE.match(ev.name)
            if kind and kind.group(1).rstrip("-") in trace_reduce.CONTROL:
                continue
            if ev.name not in scopes:
                scopes[ev.name] = innermost_scope(
                    tf_ops.get(ev.name, {}).get(NAME_STACK_STAT, ""))
            if scopes[ev.name] is not None:
                scoped = True
                stage_s[scopes[ev.name]] += (e - s) * 1e-9
                continue
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and mods[i][1] >= s and mods[i][2] == PROGRAM:
                stage_s[UNSCOPED] += (e - s) * 1e-9
        if first_ops is None:
            first_ops = ivs
    if not first_ops:
        return None
    gaps = np.asarray(trace_reduce._gaps(first_ops, lo, hi), np.float64)
    out = {"window_s": (hi - lo) * 1e-9,
           "stage_s": dict(stage_s) if scoped else None,
           "idle_s": float((gaps[:, 1] - gaps[:, 0]).sum()) * 1e-9
           if len(gaps) else 0.0,
           "idle_by_span": None}
    spans = [(max(s, lo), min(e, hi), n) for s, e, n in spans
             if e > lo and s < hi]
    if spans and len(gaps):
        out["idle_by_span"] = _idle_by_span(gaps, spans, lo, hi)
    return out


def _idle_by_span(gaps: np.ndarray, spans: list, lo: float,
                  hi: float) -> dict:
    """Idle seconds per innermost open span, cut at every span boundary."""
    pts = np.unique(np.concatenate([[lo, hi], [s for s, _, _ in spans],
                                    [e for _, e, _ in spans]]))
    idle = _idle_fn(gaps)(pts)
    seg = np.diff(idle)                  # idle ns in [pts[i], pts[i+1]]
    s0 = np.asarray([s for s, _, _ in spans])
    s1 = np.asarray([e for _, e, _ in spans])
    dur = s1 - s0
    out = defaultdict(float)
    for i, ns in enumerate(seg):
        if ns <= 0:
            continue
        a, b = pts[i], pts[i + 1]
        cover = np.nonzero((s0 <= a) & (s1 >= b))[0]
        label = (spans[cover[np.argmin(dur[cover])]][2] if len(cover)
                 else UNSPANNED)
        out[label] += ns * 1e-9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def load(trace_dir: str | None = None, notes: list | None = None) -> dict | None:
    """``reduce_profile`` of the capture in ``trace_dir`` (default: where
    ``run.py`` writes the traced window), parsed once per capture file; the
    first parse adds its breakdown to ``notes``."""
    if trace_dir is None:
        from run import TRACE_DIR
        trace_dir = str(TRACE_DIR)
    path = trace_reduce.find_xplane(trace_dir)
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _cache:
        from jax.profiler import ProfileData
        with open(path, "rb") as f:
            buf = f.read()
        r = reduce_profile(ProfileData.from_serialized_xspace(buf),
                           plane_metadata(buf))
        _cache.clear()
        _cache[key] = r
        if r is not None and notes is not None:
            notes.extend(describe(r))
    return _cache[key]


def describe(r: dict) -> list[str]:
    out = []
    if r["stage_s"]:
        out.append("wave stages s: " + " ".join(
            f"{k}={v:.6g}" for k, v in sorted(r["stage_s"].items(),
                                               key=lambda kv: -kv[1])))
    if r["idle_by_span"]:
        out.append(f"idle s by span (of {r['idle_s']:.6g}): " + " ".join(
            f"{k}={v:.6g}" for k, v in list(r["idle_by_span"].items())[:12]))
    return out


def stage_ms_per_batch(ctx, stage: str) -> float | None:
    """Device ms per batch of one wave stage (None without scopes)."""
    r = load(notes=ctx.notes)
    if r is None or not r["stage_s"] or ctx.batches == 0:
        return None
    return 1e3 * r["stage_s"].get(stage, 0.0) / ctx.batches
