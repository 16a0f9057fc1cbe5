"""RAG-flavored example: filtered retrieval over passage embeddings.

Passage embeddings are a seeded Gaussian mixture (``repro.data.synthetic``,
the shape an encoder's clustered output has), L2-normalized so L2 ranking is
cosine ranking.  FAVOR indexes them with per-passage metadata (source,
recency, length) and answers "retrieve the top-k passages semantically close
to the query, but only from source X and newer than T" -- the hybrid-query
workload of the paper's introduction.

    PYTHONPATH=src python examples/rag_retrieval.py
"""
import numpy as np

from repro.core import (AttributeTable, ColumnSpec, FavorIndex, HnswParams,
                        Schema, SearchOptions, refimpl)
from repro.core import filters as F
from repro.data import synthetic


def normalize(x):
    return x / np.linalg.norm(x, axis=1, keepdims=True)


def main():
    n_passages, dim, k = 4000, 64, 5
    embs = normalize(synthetic.make_vector_dataset(n_passages, dim, seed=5))

    # metadata: source in {0..4}, age_days in [0, 365], length float
    schema = Schema((ColumnSpec("source", "int", 5),
                     ColumnSpec("age_days", "float"),
                     ColumnSpec("length", "float")))
    rng = np.random.default_rng(1)
    attrs = AttributeTable(
        schema,
        rng.integers(0, 5, size=(n_passages, 1)).astype(np.int32),
        np.stack([rng.uniform(0, 365, n_passages),
                  rng.uniform(50, 500, n_passages)], axis=1).astype(np.float32))

    fi = FavorIndex.build(embs, attrs, HnswParams(M=12, efc=60, seed=2))
    print(f"indexed {n_passages} passages; Delta_d={fi.delta_d:.4f}")

    # questions from the same mixture as the passages (in-distribution)
    q_embs = normalize(synthetic.make_queries(8, dim, dataset_seed=5))
    flt = F.And(F.Inclusion("source", [1, 3]),       # trusted sources only
                F.Range("age_days", None, 90.0))     # fresh (< 90 days)
    res = fi.query(q_embs, flt, SearchOptions(k=k, ef=64))
    print(f"p_hat={res.p_hat[0]:.3f} route="
          f"{'brute' if res.routed_brute[0] else 'graph'}")
    mask = F.eval_program(F.compile_filter(flt, schema), attrs.ints,
                          attrs.floats)
    recall = []
    for i in range(len(q_embs)):
        got = res.ids[i][res.ids[i] >= 0]
        assert mask[got].all(), "a retrieved passage fails the filter"
        truth, _ = refimpl.bruteforce_filtered(embs, mask, q_embs[i], k)
        recall.append(refimpl.recall_at_k(res.ids[i], truth, k))
        if i < 4:
            srcs = attrs.ints[got, 0].tolist()
            ages = attrs.floats[got, 0].round(0).tolist()
            print(f"  query {i}: passages={got.tolist()} sources={srcs} "
                  f"ages={ages}")
    print(f"all retrieved passages satisfy the metadata filter; "
          f"recall@{k}={np.mean(recall):.3f}")


if __name__ == "__main__":
    main()
