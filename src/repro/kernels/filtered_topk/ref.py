"""Pure-jnp oracle for the filtered_topk kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core import filters as F

BIG = 3.0e38


def filtered_topk_ref(queries, vectors, norms, ints, floats, programs, dvec,
                      *, k: int, exclude: bool):
    """Dense (B, N) distance matrix + filter program + top-k via argsort.

    Same semantics as the kernel: PreFBF mode (exclude=False) masks failing
    rows to BIG; exclusion mode adds D per query (Eq. 2).  Rows with
    norm >= BIG (padding) never win."""
    qn = jnp.sum(queries * queries, axis=-1)
    d2 = (norms[None, :] + qn[:, None]
          - 2.0 * jnp.matmul(queries, vectors.T,
                             precision=jax.lax.Precision.HIGHEST))
    dist = jnp.sqrt(jnp.maximum(d2, 0.0))
    mask = F.eval_program_batched(programs, ints, floats, xp=jnp)  # (B, N)
    if exclude:
        dist = dist + jnp.where(mask, 0.0, dvec[:, None])
    else:
        dist = jnp.where(mask, dist, BIG)
    dist = jnp.minimum(dist, BIG)
    order = jnp.argsort(dist, axis=1)[:, :k]
    return (jnp.take_along_axis(dist, order, axis=1),
            order.astype(jnp.int32))
