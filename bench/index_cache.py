"""The built graph, kept under ``bench/.cache/index/`` between runs.

A serving replica loads a persisted index; it does not rebuild one.  The
corpus vectors belong to the configuration (``workload.make_corpus``), so the
first run of a checkout builds the program's HNSW graph over them on the host
and saves it with the program's own ``HnswIndex.save``; every later run loads
it and puts its seed's attributes beside it in a fresh ``FavorIndex``.  The
key covers the configuration file, the corpus itself and every file under
``src/``: a run after any change to the program builds anew, and never reads
a graph that another version of the program made.
"""
from __future__ import annotations

import hashlib
import json
import time
from pathlib import Path

import numpy as np

from workload import BENCH, ROOT, program_schema

CACHE = BENCH / ".cache" / "index"


def src_hash(root: Path = ROOT) -> str:
    h = hashlib.sha256()
    for p in sorted((root / "src").rglob("*")):
        if p.is_file() and "__pycache__" not in p.parts:
            h.update(str(p.relative_to(root)).encode())
            h.update(p.read_bytes())
    return h.hexdigest()


def cache_key(cfg: dict, vecs: np.ndarray, root: Path = ROOT) -> str:
    """The configuration as run (its file's content, canonically), the
    corpus vectors, and every file under ``src/``."""
    h = hashlib.sha256()
    h.update(json.dumps(cfg, sort_keys=True).encode())
    h.update(np.ascontiguousarray(vecs).tobytes())
    h.update(src_hash(root).encode())
    return h.hexdigest()[:32]


def build_spec(cfg: dict):
    from repro.core import BuildSpec, HnswParams, QuantSpec
    hn = cfg["hnsw"]
    quant = cfg.get("quant")
    return BuildSpec(
        hnsw=HnswParams(M=hn["M"], efc=hn["efc"], seed=hn.get("seed", 0)),
        quant=QuantSpec(**quant) if quant else None)


def program_attrs(cfg: dict, ints, floats):
    from repro.core import AttributeTable
    return AttributeTable(program_schema(cfg), ints, floats)


def get_index(cfg: dict, vecs, ints, floats, cache: Path | None = None):
    """(FavorIndex over ``vecs`` with these attributes, build seconds, or
    None when the graph was loaded)."""
    from repro.core import FavorIndex, HnswIndex
    path = (cache or CACHE) / cache_key(cfg, vecs)
    graph = str(path / "hnsw.npz")
    spec = build_spec(cfg)
    attrs = program_attrs(cfg, ints, floats)
    if (path / "done").exists():
        return FavorIndex(HnswIndex.load(graph), attrs, spec), None
    t0 = time.perf_counter()
    fi = FavorIndex.build(vecs, attrs, spec=spec)
    build_s = time.perf_counter() - t0
    path.mkdir(parents=True, exist_ok=True)
    fi.index.save(graph)
    (path / "done").write_text(f"{build_s}\n")
    return fi, build_s
