"""Quickstart: build a FAVOR index and run hybrid vector+attribute queries.

Uses the typed API: construction is configured by a frozen ``BuildSpec``,
each search batch by a frozen ``SearchOptions``.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core import (BuildSpec, FavorIndex, HnswParams, SearchOptions,
                        paper_filters)
from repro.core import filters as F
from repro.core import refimpl
from repro.data import synthetic


def main():
    n, dim, nq = 8000, 32, 64
    print(f"building FAVOR index: {n} vectors x {dim} dims ...")
    vecs, attrs, schema = synthetic.make_paper_dataset(n, dim, seed=0)
    fi = FavorIndex.build(vecs, attrs,
                          spec=BuildSpec(hnsw=HnswParams(M=12, efc=60, seed=0)))
    print(f"  built in {fi.build_seconds:.1f}s  Delta_d={fi.delta_d:.4f} "
          f"(Eq. 5, recorded offline)")

    queries = synthetic.make_queries(nq, dim)
    opts = SearchOptions(k=10, ef=96)
    for name, flt in paper_filters(schema).items():
        res = fi.query(queries, flt, opts)
        mask = F.eval_program(F.compile_filter(flt, schema), attrs.ints,
                              attrs.floats)
        truth = [refimpl.bruteforce_filtered(vecs, mask, q, 10)[0]
                 for q in queries]
        rec = np.mean([refimpl.recall_at_k(res.ids[i], truth[i], 10)
                       for i in range(nq)])
        route = "brute" if res.routed_brute.all() else (
            "graph" if not res.routed_brute.any() else "mixed")
        print(f"  {name:15s} p_hat={res.p_hat.mean():6.3f} route={route:6s} "
              f"recall@10={rec:.3f} qps={res.qps:8.1f}")

    # custom composite filter (Logic: AND of int equality and float range)
    custom = F.And(F.Equality("i0", 3), F.Range("f0", 20.0, 70.0))
    res = fi.query(queries[:8], custom, SearchOptions(k=5, ef=96))
    print("\ncustom filter results (ids):")
    print(res.ids)


if __name__ == "__main__":
    main()
