"""Reduce one profiler capture (``*.xplane.pb``) to the numbers the per-layer
readers use.

The benchmark marks the traced window on the host with a
``jax.profiler.TraceAnnotation`` named ``bench.window``.  A TPU plane
(``/device:TPU:<n>``) has an ``XLA Modules`` line -- one event per
execution of a compiled program, named ``jit_<name>(<fingerprint>)`` -- and
an ``XLA Ops`` line with one event per operation, named by its HLO text.
From the events inside the window it computes:

* ``busy_s`` -- the union of the operations' intervals, averaged over the
  devices; ``window_s`` -- the window's length;
* ``module_s`` / ``module_calls`` -- device seconds and executions per
  program (``jit_<name>``, fingerprint dropped);
* ``kernel_s`` -- device seconds of the operations whose HLO text names a
  ``favor.<kernel>`` scope: the Pallas kernels carry their scope name;
* ``top_ops`` -- device seconds by program and operation kind, control flow
  (``while``, ``conditional``, ``call``) left out so nothing counts twice;
* ``idle_gaps`` -- seconds with no operation on the device, by the
  innermost host event that spans the middle of each gap.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

import numpy as np

WINDOW = "bench.window"
KERNEL_RE = re.compile(r"favor\.([A-Za-z0-9_]+)")
OP_KIND_RE = re.compile(r"^%([A-Za-z_\-]+)")
CONTROL = ("while", "conditional", "call")
LABELLED = 500          # gaps named one by one, longest first


def find_xplane(trace_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def _union(intervals) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def _gaps(intervals, lo: float, hi: float):
    out, end = [], lo
    for s, e in sorted(intervals):
        if s > end:
            out.append((end, s))
        end = max(end, e)
    if hi > end:
        out.append((end, hi))
    return out


def _line(plane, name):
    for ln in plane.lines:
        if ln.name == name:
            return ln
    return None


def _module(name: str) -> str:
    return name.split("(", 1)[0]


def reduce_profile(pd, window_name: str = WINDOW) -> dict | None:
    """The summary of one ``jax.profiler.ProfileData``; None when the
    capture holds no device operation inside the window."""
    win, host = None, []
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for ln in plane.lines:
            for ev in ln.events:
                s, d = float(ev.start_ns), float(ev.duration_ns)
                if ev.name == window_name:
                    win = (s, s + d)
                elif d > 0:
                    host.append((s, s + d, ev.name))
    devices = [p for p in pd.planes if p.name.startswith("/device:TPU")]
    if win is None or not devices:
        return None
    lo, hi = win
    per_dev, first_ops = [], None
    module_s = defaultdict(float)
    module_calls = defaultdict(int)
    kernel_s = defaultdict(float)
    op_s = defaultdict(float)
    n_ops = 0
    for plane in devices:
        mods = []
        ml = _line(plane, "XLA Modules")
        for ev in (ml.events if ml is not None else ()):
            s, d = float(ev.start_ns), float(ev.duration_ns)
            if s + d <= lo or s >= hi:
                continue
            name = _module(ev.name)
            mods.append((s, s + d, name))
            module_s[name] += (min(s + d, hi) - max(s, lo)) * 1e-9
            module_calls[name] += 1
        mods.sort()
        starts = [m[0] for m in mods]
        ivs = []
        ol = _line(plane, "XLA Ops")
        for ev in (ol.events if ol is not None else ()):
            s, d = float(ev.start_ns), float(ev.duration_ns)
            if s + d <= lo or s >= hi or d <= 0:
                continue
            s, e = max(s, lo), min(s + d, hi)
            ivs.append((s, e))
            n_ops += 1
            name = ev.name
            k = KERNEL_RE.search(name)
            if k:
                kernel_s[k.group(1)] += (e - s) * 1e-9
            kind = OP_KIND_RE.match(name)
            kind = kind.group(1).rstrip("-") if kind else name[:24]
            if kind in CONTROL:
                continue
            i = bisect.bisect_right(starts, s) - 1
            mod = mods[i][2] if i >= 0 and mods[i][1] >= s else "-"
            op_s[f"{mod}:{k.group(0) if k else kind}"] += (e - s) * 1e-9
        per_dev.append(_union(ivs) * 1e-9)
        if first_ops is None:
            first_ops = ivs
    if not n_ops:
        return None
    # name the longest gaps by the innermost host event spanning their
    # middle; the many short gaps between a loop's operations go together
    gaps = sorted(_gaps(first_ops, lo, hi), key=lambda g: g[0] - g[1])
    hs = np.asarray([h[0] for h in host])
    he = np.asarray([h[1] for h in host])
    gap_s = defaultdict(float)
    longest = []
    for j, (g0, g1) in enumerate(gaps):
        if j >= LABELLED:
            gap_s["shorter gaps"] += (g1 - g0) * 1e-9
            continue
        mid = 0.5 * (g0 + g1)
        span = np.where((hs <= mid) & (he >= mid), he - hs, np.inf)
        label = host[int(np.argmin(span))][2] if np.isfinite(span).any() \
            else "none"
        gap_s[label] += (g1 - g0) * 1e-9
        if j < 10:
            longest.append((label, (g1 - g0) * 1e-9))
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(per_dev) / len(per_dev),
        "devices": len(devices),
        "n_ops": n_ops,
        "module_s": dict(module_s),
        "module_calls": dict(module_calls),
        "kernel_s": dict(kernel_s),
        "top_ops": sorted(op_s.items(), key=lambda kv: -kv[1])[:10],
        "idle_gaps": sorted(gap_s.items(), key=lambda kv: -kv[1])[:10],
        "longest_gaps": longest,
    }


def reduce_dir(trace_dir: str) -> dict | None:
    path = find_xplane(trace_dir)
    if path is None:
        return None
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path))


def describe(trace_dir: str, max_events: int = 6) -> str:
    """A readable listing of a capture's planes, lines and first events:
    for looking at one trace by hand before trusting the reduction."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(find_xplane(trace_dir))
    out = []
    for plane in pd.planes:
        lines = list(plane.lines)
        out.append(f"PLANE {plane.name} lines={[ln.name for ln in lines]}")
        for ln in lines:
            evs = list(ln.events)
            out.append(f"  LINE {ln.name} events={len(evs)}")
            for ev in evs[:max_events]:
                out.append(f"    {ev.name[:300]} start={ev.start_ns} "
                           f"dur={ev.duration_ns}")
    return "\n".join(out)
