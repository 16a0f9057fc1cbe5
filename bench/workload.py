"""What one cell runs: its configuration, its traffic mix, and the inputs both
make from ``--seed``.

Everything here is data-driven.  A configuration is ``configs/<name>.json``
(corpus shape, attribute schema, index and search settings); a traffic mix
is ``traffic/<name>.json`` (filter templates, loop kind, pool size, client
count or arrival rate).  ``BENCHMARK.json`` names the pair for each cell.

The corpus generator and the filter semantics are copies kept with the
benchmark, so a change to the program's own data or filter code cannot move
the yardstick:

* vectors: the Gaussian mixture of the FAVOR paper's section 6.1.2 (the
  shape of ``repro.data.synthetic.make_vector_dataset``).  The corpus
  vectors are the deployment's data set and belong to the configuration
  (its ``centers_seed`` and ``sample_seed``), as SIFT1M's base file does:
  so the index over them is built once per checkout, not in every run.
  The queries come from the same mixture, drawn from ``--seed``;
* attributes, drawn from ``--seed``: bool equiprobable, int uniform over
  its vocabulary, float uniform over ``[lo, hi]``;
* filters: JSON trees of ``eq`` / ``in`` / ``range`` / ``and`` leaves whose
  constants are drawn from the seed, evaluated here with numpy and handed to
  the program as its own ``repro.core`` filter objects.

Every float bound is rounded to a multiple of 1/1024, which float32 holds
exactly, so the program's float32 comparison and the float64 one here agree
on every row.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
GRID = 1024.0          # float bounds live on a 1/1024 grid (exact in float32)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass(frozen=True)
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    limits: dict


def load_cell(name: str, root: Path = ROOT, overrides: dict | None = None) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with its configuration, its
    traffic mix (found by their names) and the limits of its comparison
    (``limits/<cell>.json``).  ``overrides`` replaces
    top-level keys of the configuration and the traffic (tests shrink a
    cell with it)."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    w = cells[name]
    cfg = load_json(BENCH / "configs" / f"{w['config']}.json")
    traffic = load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    limits = load_json(BENCH / "limits" / f"{name}.json")
    for key, val in (overrides or {}).items():
        (cfg if key in cfg else traffic)[key] = val
    return Cell(name, cfg, traffic, int(w["chips"]), limits)


def rng_for(seed: int, *tags: int) -> np.random.Generator:
    """Independent streams per purpose from one ``--seed`` (any size)."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *tags]))


# stream tags: one per thing the seed makes
ATTRS, QUERIES, FILTERS, POOL, ORDER = range(2, 7)


# ---------------------------------------------------------------------------
# Corpus and queries
# ---------------------------------------------------------------------------
def _mixture(rng_c, rng, n: int, corpus: dict) -> np.ndarray:
    k, dim, std = corpus["clusters"], corpus["dim"], corpus["cluster_std"]
    centers = rng_c.normal(size=(k, dim)).astype(np.float32)
    assign = rng.integers(0, k, size=n)
    noise = rng.normal(size=(n, dim)).astype(np.float32)
    return np.ascontiguousarray(centers[assign] + np.float32(std) * noise,
                                np.float32)


def _centers(cfg: dict):
    """The mixture's centers belong to the configuration (its
    ``centers_seed``), like a data set's distribution."""
    return np.random.default_rng(cfg["corpus"]["centers_seed"])


def make_corpus(cfg: dict) -> np.ndarray:
    """The corpus vectors: the configuration's own sample of its mixture."""
    c = cfg["corpus"]
    return _mixture(_centers(cfg), np.random.default_rng(c["sample_seed"]),
                    c["n"], c)


def make_queries(cfg: dict, seed: int, n: int) -> np.ndarray:
    """Queries from the corpus's own mixture (same centers)."""
    return _mixture(_centers(cfg), rng_for(seed, QUERIES), n, cfg["corpus"])


def make_attributes(cfg: dict, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """(ints (N, n_int) int32, floats (N, n_float) float32), columns in the
    configuration's order within each kind."""
    n = cfg["corpus"]["n"]
    rng = rng_for(seed, ATTRS)
    ints, floats = [], []
    for col in cfg["attributes"]:
        if col["kind"] == "bool":
            ints.append(rng.integers(0, 2, size=n, dtype=np.int32))
        elif col["kind"] == "int":
            ints.append(rng.integers(0, col["vocab"], size=n, dtype=np.int32))
        else:
            floats.append(rng.uniform(col["lo"], col["hi"], size=n)
                          .astype(np.float32))
    ints_a = (np.stack(ints, 1) if ints else np.zeros((n, 0), np.int32))
    floats_a = (np.stack(floats, 1) if floats
                else np.zeros((n, 0), np.float32))
    return ints_a, floats_a


def column_index(cfg: dict) -> dict:
    """column name -> ("int" | "float", position in its array)."""
    out, ni, nf = {}, 0, 0
    for col in cfg["attributes"]:
        if col["kind"] == "float":
            out[col["name"]] = ("float", nf)
            nf += 1
        else:
            out[col["name"]] = ("int", ni)
            ni += 1
    return out


# ---------------------------------------------------------------------------
# Filters: templates -> concrete trees -> numpy masks / program filters
# ---------------------------------------------------------------------------
def _grid(x: float) -> float:
    return math.floor(x * GRID) / GRID


def _draw(spec, rng):
    """A literal, or a draw: {"randint": [lo, hi]} (inclusive),
    {"uniform": [lo, hi]}, {"choose": [n, k]} (k distinct of 0..n-1)."""
    if not isinstance(spec, dict):
        return spec
    if "randint" in spec:
        lo, hi = spec["randint"]
        return int(rng.integers(lo, hi + 1))
    if "uniform" in spec:
        lo, hi = spec["uniform"]
        return _grid(float(rng.uniform(lo, hi)))
    if "choose" in spec:
        n, k = spec["choose"]
        return sorted(int(v) for v in rng.choice(n, size=k, replace=False))
    raise ValueError(f"unknown draw {spec!r}")


def draw_filter(tpl: dict, rng) -> dict:
    """One concrete filter tree from a template tree."""
    op = tpl["op"]
    if op == "and":
        return {"op": "and", "children": [draw_filter(c, rng)
                                          for c in tpl["children"]]}
    if op == "eq":
        return {"op": "eq", "col": tpl["col"], "value": _draw(tpl["value"], rng)}
    if op == "in":
        return {"op": "in", "col": tpl["col"],
                "values": _draw(tpl["values"], rng)}
    if op == "range":
        lo = _draw(tpl["lo"], rng)
        width = _grid(float(_draw(tpl["width"], rng)))
        return {"op": "range", "col": tpl["col"], "lo": lo, "hi": lo + width}
    raise ValueError(f"unknown filter op {op!r}")


def eval_filter(f: dict, ints: np.ndarray, floats: np.ndarray,
                cols: dict) -> np.ndarray:
    """(N,) bool: the rows that pass ``f`` (closed float ranges)."""
    op = f["op"]
    if op == "and":
        out = np.ones(ints.shape[0], bool)
        for c in f["children"]:
            out &= eval_filter(c, ints, floats, cols)
        return out
    kind, j = cols[f["col"]]
    col = ints[:, j] if kind == "int" else floats[:, j].astype(np.float64)
    if op == "eq":
        return col == f["value"]
    if op == "in":
        return np.isin(col, f["values"])
    return (col >= f["lo"]) & (col <= f["hi"])


def to_program_filter(f: dict):
    """The program's own filter object for a concrete tree."""
    from repro.core import And, Equality, Inclusion, Range
    op = f["op"]
    if op == "and":
        return And(*(to_program_filter(c) for c in f["children"]))
    if op == "eq":
        return Equality(f["col"], f["value"])
    if op == "in":
        return Inclusion(f["col"], f["values"])
    return Range(f["col"], f["lo"], f["hi"])


def program_schema(cfg: dict):
    from repro.core import ColumnSpec, Schema
    return Schema(tuple(ColumnSpec(c["name"], c["kind"], c.get("vocab"))
                        for c in cfg["attributes"]))


# ---------------------------------------------------------------------------
# Pool and arrivals
# ---------------------------------------------------------------------------
@dataclass
class Pool:
    """``size`` (query, filter) pairs: ``queries`` (P, d) and, per item, the
    index of its concrete filter in ``filters``."""
    queries: np.ndarray
    filter_of: np.ndarray
    filters: list

    @property
    def size(self) -> int:
        return len(self.filter_of)


def make_pool(cfg: dict, traffic: dict, seed: int) -> Pool:
    """Filters whose template says ``"draw": "seed"`` are drawn once for the
    run (the paper's six scenarios); ``"draw": "request"`` templates give
    every pool item its own constants.  Items pick templates by weight."""
    size = traffic["pool"]
    rng_f = rng_for(seed, FILTERS)
    rng_p = rng_for(seed, POOL)
    tpls = traffic["filters"]
    weights = np.asarray([t.get("weight", 1.0) for t in tpls], np.float64)
    choice = rng_p.choice(len(tpls), size=size, p=weights / weights.sum())
    filters: list = []
    per_seed = {}
    for i, t in enumerate(tpls):
        if t["draw"] == "seed":
            per_seed[i] = len(filters)
            filters.append(draw_filter(t["filter"], rng_f))
    filter_of = np.empty(size, np.int64)
    for j, i in enumerate(choice):
        if tpls[i]["draw"] == "seed":
            filter_of[j] = per_seed[i]
        else:
            filter_of[j] = len(filters)
            filters.append(draw_filter(tpls[i]["filter"], rng_f))
    return Pool(make_queries(cfg, seed, size), filter_of, filters)


def arrival_times(traffic: dict, seconds: float, seed: int) -> np.ndarray:
    """Open loop: Poisson due times in [0, seconds).  The inter-arrival
    gaps are one fixed draw (the mix's own ``arrival_seed``), shuffled by
    ``--seed``: every seed offers the same number of requests over the
    window, in another order."""
    rate = float(traffic["rate"])
    n = int(round(rate * seconds))
    gaps = np.random.default_rng(traffic["arrival_seed"]).exponential(
        1.0 / rate, size=n)
    gaps *= seconds / gaps.sum()        # exactly n arrivals in the window
    gaps = rng_for(seed, ORDER, 1).permutation(gaps)
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])
