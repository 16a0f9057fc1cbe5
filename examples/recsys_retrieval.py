"""FAVOR as the recsys candidate-retrieval layer.

Item and user embeddings are a seeded Gaussian mixture
(``repro.data.synthetic``), L2-normalized so L2 ranking is cosine ranking.
Each user asks for the nearest items under an attribute filter (stock,
category and price predicates), served two ways:
  1. the filtered brute route (PreFBF), jnp and the Pallas ``filtered_topk``
     kernel side by side -- both exact, identical ids;
  2. a ServeEngine over the FAVOR index, routing every user's request by its
     estimated selectivity (graph or brute) in batches.

    PYTHONPATH=src python examples/recsys_retrieval.py
"""
import time

import numpy as np

from repro.core import (FavorIndex, HnswParams, LocalBackend, SearchOptions,
                        paper_schema, random_attributes, refimpl)
from repro.core import filters as F
from repro.data import synthetic
from repro.serving import ServeEngine


def normalize(x):
    return x / np.linalg.norm(x, axis=1, keepdims=True)


def main():
    n_items, d, k, n_users = 10000, 32, 50, 16
    items = normalize(synthetic.make_vector_dataset(n_items, d, seed=0))
    users = normalize(synthetic.make_queries(n_users, d, dataset_seed=0))
    schema = paper_schema()        # b0 = in_stock, i0 = category, f0 = price
    attrs = random_attributes(schema, n_items, seed=1)
    fi = FavorIndex.build(items, attrs, HnswParams(M=12, efc=60, seed=2))

    flt = F.And(F.Equality("b0", True),          # in stock
                F.Inclusion("i0", [2, 5, 7]),    # category in {2,5,7}
                F.Range("f0", 10.0, 80.0))       # price band
    mask = F.eval_program(F.compile_filter(flt, schema), attrs.ints,
                          attrs.floats)
    truth = [refimpl.bruteforce_filtered(items, mask, u, k)[0] for u in users]

    def recall(ids):
        return np.mean([refimpl.recall_at_k(ids[i], truth[i], k)
                        for i in range(n_users)])

    brute = SearchOptions(k=k, force="brute")
    brute_ids = []
    for name, opts in (("jnp brute scan", brute),
                       ("Pallas filtered_topk", brute.with_(use_pallas=True))):
        t0 = time.perf_counter()
        res = fi.query(users, flt, opts)
        print(f"{name:22s} {time.perf_counter() - t0:.3f}s "
              f"recall@{k}={recall(res.ids):.3f}")
        brute_ids.append(res.ids)
    print(f"  (Pallas in interpret mode on CPU) identical ids: "
          f"{bool((brute_ids[0] == brute_ids[1]).all())}")

    # at p ~= 10% the result pool must reach ~k/p neighbors: ef >> 2k
    eng = ServeEngine(LocalBackend(fi), SearchOptions(k=k, ef=8 * k),
                      max_batch=8)
    for u in users:
        eng.submit(u, flt)
    out = sorted(eng.run(), key=lambda r: r.rid)
    ids = np.stack([r.ids for r in out])
    print(f"ServeEngine:            recall@{k}={recall(ids):.3f} "
          f"(p_hat={out[0].p_hat:.3f}, routes={eng.stats['graph']} graph "
          f"/ {eng.stats['brute']} brute)")


if __name__ == "__main__":
    main()
