"""Helpers the per-layer readers under ``metrics/`` share.

A reader is ``metrics/<metric name>.py`` with ``read(ctx) -> float | None``.
``ctx`` (``Context``) holds what one traced run saw over its traced window:
the program's counters and spans (``window_registry``), the reduced device
trace, the configuration and the chip's peaks.  A reader that finds nothing to read
returns None, and the run leaves that metric out of its line.
"""
from __future__ import annotations

import json
from dataclasses import dataclass, field

from workload import BENCH


@dataclass
class Context:
    cfg: dict
    traffic: dict
    registry: dict            # what the program counted in the traced window
    trace: dict | None        # trace_reduce.reduce_profile(), or None
    device_kind: str
    notes: list = field(default_factory=list)   # printed before the result

    # -- program counters and spans ------------------------------------------
    def counter(self, name: str, labels: str = "") -> float:
        series = self.registry["counters"].get(name, {}).get("series", {})
        return float(series.get(labels, 0.0))

    def hist(self, name: str, labels: str = "") -> tuple[float, int]:
        """(sum, count) of one histogram series."""
        series = self.registry["histograms"].get(name, {}).get("series", {})
        s = series.get(labels)
        return (float(s["sum"]), int(s["count"])) if s else (0.0, 0)

    def stage_s(self, stage: str) -> float:
        return self.hist("favor_stage_seconds", f'stage="{stage}"')[0]

    @property
    def batches(self) -> int:
        return int(self.counter("favor_batches_total"))

    def view(self, name: str) -> dict:
        return self.registry["views"].get(name, {})

    # -- device trace ----------------------------------------------------------
    def kernel_s(self, kernel: str) -> float | None:
        """Device seconds of the Pallas kernel ``favor.<kernel>``."""
        if self.trace is None:
            return None
        return self.trace["kernel_s"].get(kernel)

    def module_s(self, module: str) -> float | None:
        """Device seconds of the compiled program ``jit_<module>``."""
        if self.trace is None:
            return None
        return self.trace["module_s"].get(f"jit_{module}")

    def module_calls(self, module: str) -> int:
        """Executions of the compiled program ``jit_<module>``."""
        if self.trace is None:
            return 0
        return self.trace["module_calls"].get(f"jit_{module}", 0)

    def peaks(self) -> dict:
        with open(BENCH / "peaks.json") as f:
            table = json.load(f)["devices"]
        if self.device_kind not in table:
            raise KeyError(f"no peaks for device kind {self.device_kind!r} "
                           f"in peaks.json (have {sorted(table)})")
        return table[self.device_kind]


def window_registry(before: dict, after: dict) -> dict:
    """What the program counted between two ``MetricsRegistry.snapshot()``
    calls: counters, histogram sums and counts, and the front end's
    dispatch ledger, each as the difference."""
    out = {"counters": {}, "histograms": {}, "views": dict(after["views"])}
    for name, m in after["counters"].items():
        b = before["counters"].get(name, {}).get("series", {})
        out["counters"][name] = {"series": {k: v - b.get(k, 0.0)
                                            for k, v in m["series"].items()}}
    for name, m in after["histograms"].items():
        b = before["histograms"].get(name, {}).get("series", {})
        out["histograms"][name] = {"series": {
            k: {"sum": v["sum"] - b.get(k, {}).get("sum", 0.0),
                "count": v["count"] - b.get(k, {}).get("count", 0)}
            for k, v in m["series"].items()}}
    co_a = after["views"].get("frontend", {}).get("coalesce")
    co_b = before["views"].get("frontend", {}).get("coalesce", {})
    if co_a:
        out["views"]["frontend"] = dict(after["views"]["frontend"], coalesce={
            "dispatches": co_a["dispatches"] - co_b.get("dispatches", 0),
            "rows": co_a["rows"] - co_b.get("rows", 0)})
    return out


def per_batch_ms(ctx: Context, seconds: float | None) -> float | None:
    if seconds is None or ctx.batches == 0:
        return None
    return 1e3 * seconds / ctx.batches


def host_ms_per_batch(ctx: Context) -> float | None:
    """Host time of the engine's step outside the selectivity estimate and
    the routes' device dispatch: filter compile, cache lookup, routing,
    sub-batch slicing and padding (``favor_stage_seconds``).  Each route's
    span less its ``search`` child (``graph/search``, ``brute/search``),
    which enqueues that route's device work."""
    if ctx.batches == 0:
        return None
    host = sum(ctx.stage_s(s) for s in ("compile", "cache_lookup", "route",
                                        "graph", "brute"))
    dispatch = ctx.stage_s("graph/search") + ctx.stage_s("brute/search")
    return per_batch_ms(ctx, host - dispatch)


def batch_fill(ctx: Context) -> float | None:
    """Rows per front-end dispatch, as a share of the dispatch cap."""
    co = ctx.view("frontend").get("coalesce", {})
    if not co.get("dispatches"):
        return None
    cap = ctx.cfg["frontend"].get("max_batch") or ctx.cfg["engine"]["max_batch"]
    return 100.0 * co["rows"] / co["dispatches"] / cap


def device_idle(ctx: Context) -> float | None:
    if ctx.trace is None or ctx.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])


def roofline(ctx: Context, name: str, seconds: float | None, ops: float,
             nbytes: float, op_peak: str = "flops_bf16") -> float | None:
    """Share (%) of the least time the chip needs for ``ops`` operations
    and ``nbytes`` bytes -- the larger of ops over the peak rate and bytes
    over the memory bandwidth -- in the ``seconds`` the kernel took.  The
    bound that sets the least time goes into ``ctx.notes``."""
    if not seconds or ops <= 0:
        return None
    pk = ctx.peaks()
    t_ops, t_bytes = ops / pk[op_peak], nbytes / pk["hbm_bytes_per_s"]
    ctx.notes.append(f"{name}: ops={ops:.6g} bytes={nbytes:.6g} "
                     f"kernel_s={seconds:.6g} bound="
                     f"{'bytes' if t_bytes >= t_ops else op_peak}")
    return 100.0 * max(t_ops, t_bytes) / seconds


def attr_bytes(cfg: dict) -> int:
    """Bytes of one row's attribute columns (int32 / float32 each)."""
    return 4 * len(cfg["attributes"])
