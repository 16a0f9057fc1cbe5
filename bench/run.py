#!/usr/bin/env python3
"""FAVOR as a filtered vector-search service on one chip: one cell, one run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration (``configs/``) and a
traffic mix (``traffic/``).  One run:

1. makes the configuration's corpus and, from ``--seed``, its attributes,
   the (query, filter) pool and, for an open loop, the arrival times;
2. loads the corpus's graph from ``bench/.cache`` or, in the first run of
   a checkout, builds it (``index_cache``);
3. puts the served path together, ``FrontEnd(ServeEngine(LocalBackend))``,
   and compiles every shape the traffic will dispatch (``serve.warm``);
4. drives ``FrontEnd.submit`` for ``--seconds`` (the window), then waits
   for every request sent in it; with ``--trace 1`` the profiler records
   the window's first ``TRACE_SECONDS``;
5. reads the device's peak memory, frees the program's state, and checks
   every answer of the window against the exact reference (``reference``);
6. prints the end-to-end metrics (``--trace 0``) or the per-layer ones
   (``--trace 1``) as the last line: one JSON object.

Set-up (``setup_s``) is everything before the window, from the start of the
process: imports, data, index load or build, warm-up.  The run exits
non-zero, with no result line, when JAX finds no TPU or fewer chips than
the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

import index_cache  # noqa: E402
import reference  # noqa: E402
import serve  # noqa: E402
import trace_reduce  # noqa: E402
import workload  # noqa: E402
import layer  # noqa: E402

COMPILE_CACHE = BENCH / ".cache" / "jax"
TRACE_DIR = BENCH / ".cache" / "trace"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_SECONDS = 3.0    # the profiler records the first seconds of the window


def say(*parts) -> None:
    print(*parts, flush=True)


def note(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


class CompileCount:
    """Executables JAX compiled or fetched from its persistent cache."""

    def __init__(self):
        self.n = 0

    def __call__(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.n += 1


_COMPILES: CompileCount | None = None


def compile_count() -> CompileCount:
    global _COMPILES
    if _COMPILES is None:
        from jax import monitoring
        _COMPILES = CompileCount()
        monitoring.register_event_duration_secs_listener(_COMPILES)
    return _COMPILES


def enable_compile_cache(jax) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout;
    every program is kept, however quickly it compiled.  No size limit:
    with one, JAX's eviction scan races the engine's two dispatch threads
    (an entry without its access-time file fails every later write)."""
    COMPILE_CACHE.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def metric_specs(cell_name: str, section: str) -> list:
    bench = workload.load_json(ROOT / "BENCHMARK.json")
    return [m for m in bench[section]
            if "workloads" not in m or cell_name in m["workloads"]]


def load_reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


async def session(fe, eng, cell, pool, args, times, trace: bool):
    """Warm-up, then the window.  Returns what the window saw."""
    import jax
    cfg, traffic = cell.config, cell.traffic
    k = cfg["search"]["k"]
    warm_log = serve.Requests(k)
    sub = serve.Submitter(fe, pool, warm_log)
    warm = await serve.warm(sub, eng, traffic, cfg["engine"]["max_batch"])
    log = serve.Requests(k)
    sub.log = log
    eng.reset_stats()
    compiles = compile_count()
    seen = {}
    loop = asyncio.get_running_loop()

    def on_start():
        seen["setup_s"] = time.perf_counter() - T_START
        seen["compiles0"] = compiles.n
        if trace:
            ann = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
            ann.__enter__()
            before = eng.obs.registry.snapshot()

            def stop_trace():
                ann.__exit__(None, None, None)
                seen["registry"] = layer.window_registry(
                    before, eng.obs.registry.snapshot())
                jax.profiler.stop_trace()
            loop.call_later(min(TRACE_SECONDS, args.seconds), stop_trace)


    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # Python-level tracing slows the
        opts.host_tracer_level = 2          # host tenfold; annotations stay
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    try:
        if traffic["loop"] == "closed":
            t0, t_end = await serve.closed_loop(sub, traffic["clients"],
                                                args.seconds, args.seed,
                                                on_start)
            late = None
        else:
            t0, t_end, late = await serve.open_loop(sub, times, args.seconds,
                                                    args.seed, on_start)
    finally:
        await fe.close()
    seen["compiles"] = compiles.n - seen["compiles0"]
    seen.update(t0=t0, t_end=t_end, late=late, log=log, warm=warm)
    return seen


def e2e_metrics(cell, seen, diag, args) -> dict:
    log = seen["log"]
    done = np.asarray(log.done, np.float64)
    ok = np.asarray([s == "ok" for s in log.status], bool)
    vals = {"setup_s": seen["setup_s"], "recall_at_10": 100.0 * diag["recall"]}
    # every request sent in the window, over the time from its start to the
    # last answer: a closed loop completes whole batches, and a count cut at
    # the window's close would move in steps of one batch
    t_last = float(done[ok].max()) if ok.any() else seen["t_end"]
    vals["qps"] = float(ok.sum()) / (t_last - seen["t0"])
    if cell.traffic["loop"] == "open":
        # a request shed or never answered misses any limit: it counts as
        # the longest wait the run allows
        cap = (args.seconds + serve.SETTLE_S) * 1e3
        lat = np.where(ok, (done - np.asarray(log.due)) * 1e3, cap)
        vals["p50_ms"] = float(np.percentile(lat, 50, method="higher"))
        vals["p99_ms"] = float(np.percentile(lat, 99, method="higher"))
    return {m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
            for m in metric_specs(cell.name, "end_to_end")}


def layer_metrics(cell, seen, tr, kind) -> tuple[dict, list]:
    ctx = layer.Context(cell.config, cell.traffic, seen["registry"], tr, kind)
    out = {}
    for m in metric_specs(cell.name, "per_layer"):
        v = load_reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out, ctx.notes


def main(argv=None, expect_platform: str = "tpu",
         overrides: dict | None = None) -> int:
    args = parse(argv)
    cell = workload.load_cell(args.workload, overrides=overrides)
    cfg, traffic = cell.config, cell.traffic

    import jax
    enable_compile_cache(jax)
    devs = jax.devices()
    kind = devs[0].device_kind
    if devs[0].platform != expect_platform or len(devs) < cell.chips:
        note(f"no run: cell {cell.name} needs {cell.chips} {expect_platform} "
             f"device(s); JAX found {len(devs)} {devs[0].platform} "
             f"({kind})")
        return 2
    say(f"device platform={devs[0].platform} kind={kind} count={len(devs)}")

    vecs = workload.make_corpus(cfg)
    ints, floats = workload.make_attributes(cfg, args.seed)
    pool = workload.make_pool(cfg, traffic, args.seed)
    times = (workload.arrival_times(traffic, args.seconds, args.seed)
             if traffic["loop"] == "open" else None)
    t_data = time.perf_counter() - T_START
    fi, build_s = index_cache.get_index(cfg, vecs, ints, floats)
    say(f"index n={vecs.shape[0]} d={vecs.shape[1]} "
        + (f"built build_s={build_s:.3f}" if build_s is not None
           else "loaded from cache")
        + f" (imports and data {t_data:.3f} s, index ready at "
          f"{time.perf_counter() - T_START:.3f} s)")
    fe, eng = serve.build_stack(cfg, fi)
    seen = asyncio.run(session(fe, eng, cell, pool, args, times,
                               bool(args.trace)))
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs[:cell.chips])
    del fe, eng, fi
    gc.collect()

    log = seen["log"]
    status = np.asarray(log.status, object)
    answered = status == "ok"
    unanswered = int(np.sum((status == None) | (status == "error")))  # noqa: E711
    items = np.asarray(log.items, np.int64)[answered]
    k = cfg["search"]["k"]
    ans_ids = (np.stack([log.ids[i] for i in np.nonzero(answered)[0]])
               if answered.any() else np.zeros((0, k), np.int64))
    ans_d = (np.stack([log.dists[i] for i in np.nonzero(answered)[0]])
             if answered.any() else np.zeros((0, k)))
    ans_brute = np.asarray([log.routes[i] == "brute"
                            for i in np.nonzero(answered)[0]], bool)
    cols = workload.column_index(cfg)
    # the program's brute route scans compressed codes where ``use_pq``
    # (``LocalBackend.search_brute``): it promises recall, not exactness
    numbers, diag = reference.compare(items, ans_ids, ans_d, ans_brute,
                                      unanswered, vecs, ints, floats, cols,
                                      pool, k,
                                      brute_exact=not cfg["search"]["use_pq"])
    correct = reference.judge(numbers, cell.limits)

    routes = [r for r in log.routes if r is not None]
    say(f"warm-up: {seen['warm']}; window: compiles={seen['compiles']} "
        f"requests={len(log.items)} answered={int(answered.sum())} "
        f"shed={int(np.sum(status == 'shed'))} "
        f"graph={routes.count('graph')} brute={routes.count('brute')}")
    if seen["late"] is not None and len(seen["late"]):
        say(f"generator lateness ms: p50={np.percentile(seen['late'], 50) * 1e3:.3f} "
            f"p99={np.percentile(seen['late'], 99) * 1e3:.3f} "
            f"max={seen['late'].max() * 1e3:.3f}")
    say(f"reference: {diag}")
    if log.errors:
        say(f"errors: {len(log.errors)}, first: {log.errors[0]}")
    say(f"memory peak_bytes_in_use={peak}")

    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": len(log.items),
              "failed": int(len(log.items) - answered.sum())}
    if args.trace:
        tr = trace_reduce.reduce_dir(str(TRACE_DIR))
        metrics, notes = layer_metrics(cell, seen, tr, kind)
        for n in notes:
            say(n)
        if tr is not None:
            device["busy_s"] = tr["busy_s"]
            device["window_s"] = tr["window_s"]
            say(f"trace: ops={tr['n_ops']} kernels={tr['kernel_s']} "
                f"modules={tr['module_s']} longest gaps={tr['longest_gaps']}")
            result["breakdown"] = {"device_ops": tr["top_ops"],
                                   "idle_gaps": tr["idle_gaps"]}
    else:
        metrics = e2e_metrics(cell, seen, diag, args)
    result.update(metrics=metrics, device=device)
    result["checks"] = {name: {"value": numbers[name],
                               "limit": cell.limits[name]}
                        for name in numbers}
    for name in numbers:
        note(f"check {name}={numbers[name]!r} limit={cell.limits[name]!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
