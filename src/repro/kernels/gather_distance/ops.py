"""Public jit'd wrapper for the gather_distance kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .. import default_interpret
from ..filtered_topk.ops import (SUBLANES, _pad_rows, _round_up, attr_planes,
                                 kernel_programs)
from .kernel import BIG, gather_distance_pallas


@partial(jax.jit, static_argnames=("interpret",))
def gather_distance(vectors, norms, ints, floats, queries, nbr_ids, programs,
                    dvec, *, interpret: bool | None = None, valid=None):
    """Graph-expansion distance evaluation (Pallas).

    ``norms`` (B, M), ``ints`` (B, M, mi) and ``floats`` (B, M, mf) are the
    neighbour rows' per-row scalars, gathered by the caller at the clamped
    ids (the traversal reads them from its side rows).  The neighbour
    vectors are gathered here by XLA (clamped ids), in the kernel's layout;
    the kernel fuses distance, filter and exclusion over each tile of
    ``SUBLANES`` queries.  ``valid`` is an optional (B,) bool query mask
    (bucket padding): False rows return all-+inf distances and no TD hits.
    Returns (dbar (B, M) f32 -- +inf at -1 padding, td (B, M) bool)."""
    if interpret is None:
        interpret = default_interpret()
    b, m = nbr_ids.shape
    b_pad = _round_up(b, SUBLANES)
    ids = _pad_rows(nbr_ids.astype(jnp.int32), b_pad, -1)
    safe = jnp.maximum(ids, 0)
    # attribute planes of the block, (mi, B*M) / (mf, B*M)
    ints_g, floats_g = attr_planes(ints.reshape(b * m, ints.shape[-1]),
                                   floats.reshape(b * m, floats.shape[-1]),
                                   b_pad * m)
    # HLO-metadata profiling scope, the innermost around the pallas_call:
    # the device trace names the kernel by it (trace-time only)
    with jax.named_scope("favor.gather_distance"):
        out_d, out_td = gather_distance_pallas(
            ids, _pad_rows(queries, b_pad, 0),
            vectors[safe.reshape(-1)],
            _pad_rows(norms.astype(jnp.float32), b_pad, 0),
            ints_g.reshape(-1, b_pad, m), floats_g.reshape(-1, b_pad, m),
            kernel_programs(programs, b_pad),
            _pad_rows(dvec.astype(jnp.float32), b_pad, 0).reshape(b_pad, 1),
            block_q=SUBLANES, interpret=interpret)
    out_d = jnp.where(out_d[:b] >= BIG, jnp.inf, out_d[:b])
    out_td = out_td[:b].astype(bool)
    if valid is not None:
        vmask = jnp.asarray(valid, bool)[:, None]
        out_d = jnp.where(vmask, out_d, jnp.inf)
        out_td = out_td & vmask
    return (out_d, out_td)
