"""Benchmark harness entry point: one module per paper table/figure.

``python -m benchmarks.run [--quick] [--only name]``

Emits per-benchmark CSVs to bench_out/ and a ``name,us_per_call,derived``
summary to stdout (derived = the benchmark's headline metric/CSV path).
A failed benchmark does not stop the others, but the run exits non-zero.
Compiled programs persist in JAX's compile cache (``repro.compile_cache``).
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback
from pathlib import Path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache(Path(__file__).resolve().parents[1])
    from . import (bench_ablation, bench_cache, bench_qps_recall, bench_quant,
                   bench_selectivity, bench_serve_backends,
                   bench_verification)

    benches = [
        ("qps_recall_figs4_5_8_9", bench_qps_recall.run),
        # graph-route scorer layer: f32 vs PQ-ADC traversal (core.scoring)
        ("graph_scorers", bench_qps_recall.run_scorers),
        ("quant_pq_adc", bench_quant.run),
        ("serve_backends", bench_serve_backends.run),
        # also emits the stable cross-PR serving summary BENCH_serve.json
        ("serve_cache_zipf", bench_cache.run),
        ("selectivity_fig7", bench_selectivity.run),
        ("exclusion_ablation_fig10", bench_ablation.run_exclusion),
        ("termination_fig11", bench_ablation.run_termination),
        ("recall_levels_fig6", bench_verification.run_recall_levels),
        ("construction_tabs4_5", bench_verification.run_construction),
        ("search_path_figs12_13", bench_verification.run_search_path),
        ("linear_model_tab6", bench_verification.run_linear_model),
    ]
    print("name,us_per_call,derived")
    failed = []
    for name, fn in benches:
        if args.only and args.only not in name:
            continue
        t0 = time.perf_counter()
        try:
            out = fn(quick=args.quick)
            dt = (time.perf_counter() - t0) * 1e6
            print(f"{name},{dt:.0f},{out}")
        except Exception as e:
            traceback.print_exc()
            print(f"{name},-1,FAILED:{type(e).__name__}")
            failed.append(name)
    if failed:
        sys.exit(f"{len(failed)} benchmark(s) failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
