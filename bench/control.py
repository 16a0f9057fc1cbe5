#!/usr/bin/env python3
"""Readings that set the limits of ``correct``: the two things the comparison
has to catch, at a cell's own size, for several seeds.

    python bench/control.py --workload sift128-f32.paper.closed --seeds 1,2,3
    python bench/control.py --workload sift128-f32.paper.closed --seeds 1,2,3 \\
        --fault ef_k --seconds 10

``--fault control`` (the default): the reference put in the program's place
one precision step down (``reference.control_answers``), over every item of
the pool, on the host.  ``--fault ef_k``: the served path as the cell runs
it, on the chip, with the traversal's search width cut to ``k`` -- a graph
route that ignores ``ef``.  ``--fault rerank_k``: its compressed-route
counterpart, for a cell whose brute route scans PQ or SQ codes
(``search.use_pq``): the served path with ``search.rerank = 0``, so that only
the codes' own top ``k`` get exact distances.  Each served fault is one run
of ``run.py`` per seed, in this process, with a window of ``--seconds``.

Prints, per seed, the numbers ``correct`` compares and their limits.  A
sound limit lies below the reading that each fault is there to raise.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH)]

import numpy as np  # noqa: E402

import reference  # noqa: E402
import workload  # noqa: E402


def control_readings(cell, seed: int) -> tuple[dict, dict]:
    cfg = cell.config
    vecs = workload.make_corpus(cfg)
    ints, floats = workload.make_attributes(cfg, seed)
    pool = workload.make_pool(cfg, cell.traffic, seed)
    cols = workload.column_index(cfg)
    k = cfg["search"]["k"]
    items = np.arange(pool.size)
    ids, dists = reference.control_answers(vecs, ints, floats, cols, pool, k,
                                           items)
    # every control answer is a full exact scan: hold it to that rule
    return reference.compare(items, ids, dists, np.ones(len(items), bool), 0,
                             vecs, ints, floats, cols, pool, k,
                             brute_exact=True)


def served_readings(cell, seed: int, seconds: float, change: dict,
                    expect_platform: str = "tpu",
                    overrides: dict | None = None) -> tuple[dict, dict]:
    """One run of the served path with ``change`` applied to the
    configuration's ``search`` settings (after ``overrides``)."""
    import run
    ov = dict(overrides or {})
    ov["search"] = dict(ov.get("search", cell.config["search"]), **change)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = run.main(["--workload", cell.name, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", "0"],
                      expect_platform=expect_platform, overrides=ov)
    lines = buf.getvalue().splitlines()
    if rc != 0 or not lines or not lines[-1].startswith("{"):
        raise SystemExit(f"run failed (rc {rc}): {lines[-3:]}")
    last = json.loads(lines[-1])
    numbers = {n: c["value"] for n, c in last["checks"].items()}
    recall = last["metrics"]["recall_at_10"]["value"] / 100.0
    return numbers, {"recall": recall, "attempted": last["attempted"]}


def ef_k_readings(cell, seed: int, seconds: float,
                  expect_platform: str = "tpu",
                  overrides: dict | None = None) -> tuple[dict, dict]:
    search = (overrides or {}).get("search", cell.config["search"])
    return served_readings(cell, seed, seconds, {"ef": search["k"]},
                           expect_platform, overrides)


def rerank_k_readings(cell, seed: int, seconds: float,
                      expect_platform: str = "tpu",
                      overrides: dict | None = None) -> tuple[dict, dict]:
    ov = overrides or {}
    if not (ov.get("search", cell.config["search"])["use_pq"]
            and ov.get("quant", cell.config.get("quant"))):
        raise SystemExit(f"--fault rerank_k: {cell.name} has no compressed "
                         f"brute route (search.use_pq and quant)")
    return served_readings(cell, seed, seconds, {"rerank": 0},
                           expect_platform, overrides)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--fault", choices=("control", "ef_k", "rerank_k"),
                    default="control")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    cell = workload.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",") if s):
        if args.fault == "control":
            numbers, diag = control_readings(cell, seed)
        elif args.fault == "ef_k":
            numbers, diag = ef_k_readings(cell, seed, args.seconds)
        else:
            numbers, diag = rerank_k_readings(cell, seed, args.seconds)
        print(json.dumps({"workload": cell.name, "fault": args.fault,
                          "seed": seed, "numbers": numbers,
                          "limits": cell.limits,
                          "correct": reference.judge(numbers, cell.limits),
                          "recall": diag["recall"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
