"""Pallas TPU kernels for FAVOR's compute hot spots.

Each kernel package ships three files:
  kernel.py -- pl.pallas_call body with explicit BlockSpec VMEM tiling
  ops.py    -- jit'd public wrapper (padding, kernel layouts, interpret
               auto-detection: interpret=True on CPU, compiled on TPU)
  ref.py    -- pure-jnp oracle used by the shape/dtype sweep tests

Kernels:
  filtered_topk   -- fused L2 distance + filter-program mask + exclusion
                     distance + running top-k (PreFBF / retrieval_cand path)
  gather_distance -- scalar-prefetch neighbor gather + distance + exclusion
                     (graph-search expansion; paged-attention indirection idiom)
  embedding_bag   -- scalar-prefetch row gather + segment-sum bag reduce
                     (recsys embedding lookup; JAX has no native EmbeddingBag)
  pq_adc          -- fused PQ asymmetric-distance LUT accumulate + filter
                     mask + running top-R over uint8 code chunks (the
                     compressed PreFBF scan; quant/adc.py re-ranks exactly)
"""
import jax


def default_interpret() -> bool:
    """Pallas interpret mode on CPU (validation), compiled on TPU (target).
    Any other platform is an error: the kernels are written for the TPU."""
    platform = jax.default_backend()
    if platform not in ("cpu", "tpu"):
        raise RuntimeError(f"Pallas kernels run compiled on TPU or "
                           f"interpreted on CPU, not on {platform!r}")
    return platform == "cpu"
