"""JAX production search: batched FAVOR graph traversal on TPU.

TPU-native realization of Algorithms 2 + 3 (DESIGN.md section 3):

 * the query batch runs as ONE ``lax.while_loop`` whose state carries a lane
   per query; finished lanes are masked, the loop ends when all lanes do;
 * the candidate set C and result set R are fixed-capacity distance-sorted
   pools updated by a stable rank-and-select merge of (pool ||
   new-neighbor-block) with no sort or gather (``_merge_pool``) -- no
   dynamic heaps.  C capacity = ``cand_cap`` (default ef) is the
   bounded-memory approximation of the paper's unbounded heap; recall
   parity with the refimpl oracle is asserted in tests and measured in
   benchmarks;
 * neighbor-block scoring is pluggable (``core.scoring``): the same
   traversal body runs full-precision f32 (ExactScorer), PQ asymmetric
   distances over gathered uint8 codes (PqAdcScorer: the ADC LUT is built
   once per query before the loop) or dequantized int8 (SqScorer),
   selected by the jit-static ``SearchConfig.graph_quant``;
 * the exclusion distance (Eq. 2) composes *on top of* whatever the scorer
   returns (``scoring.exclusion_compose``); quantized scorers get an exact
   f32 re-rank of the final top-``graph_rerank * k`` TD candidates (the
   same pass the brute route uses, quant/adc.py);
 * termination implements section 5.4: the usual adjusted-distance condition
   AND the TD-fraction guard ``pbar > pbar_min`` (0 disables);
 * the visited set is a packed per-query uint32 bitfield
   ``(B, ceil(N/32))`` -- 8x less HBM per lane than the former (B, N) bool
   bitmap at multi-million-N scale;
 * the while_loop is *lane-compacted* (``SearchConfig.lane_compact``): a
   static ladder of stage widths B, B/2, ... -- each stage exits once the
   active-lane population fits the next, survivors are packed into a
   half-width batch, and finished lanes stop costing wave work.  Results
   are bit-identical to the single-stage loop because every per-lane op is
   row-wise and every scorer is bit-stable across batch sizes.

``favor_graph_search`` (exclusion distances) and ``rsf_graph_search``
(result-set-filtering baseline: D = 0, R admits TD only) are two thin
entry points over ONE parameterized traversal body, so they stay in
lockstep on the lane-mask (bucket padding) contract and the hops/path_td
diagnostics.

Everything here is jit/shard_map friendly: shapes static, no host callbacks.
"""
from __future__ import annotations

import weakref
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import filters as F
from .hnsw import HnswIndex
from .scoring import exclusion_compose, pairwise_dist, scorer_for

INF = jnp.inf

# back-compat alias: callers (and the batching docs) reference the
# mul+reduce pairwise distance by its historical private name
_pairwise_dist = pairwise_dist


@dataclass(frozen=True)
class SearchConfig:
    k: int = 10
    ef: int = 100
    cand_cap: int = 0          # 0 -> ef
    max_steps: int = 0         # 0 -> 8 * ef safety bound
    pbar_min: float = 0.5      # section 5.4 threshold (0 disables)
    gamma: float = 1.0         # Algorithm 3 line 8 slack
    use_pallas: bool = False   # route scoring through the Pallas kernels
    graph_quant: str | None = None  # None (f32) | "pq" | "sq" scorer
    graph_rerank: int = 4      # exact-re-rank depth: top max(k, rr*k) TD
                               # candidates, capped at ef (quantized only)
    lane_compact: int = 2      # halve the wave width whenever the active-lane
                               # population fits the next stage, down to this
                               # floor (0 disables; results are bit-identical).
                               # 2 keeps straggler waves cheap -- quantized
                               # scorers run ~1.7x more waves than f32 (noisy
                               # distances delay termination), almost all in
                               # the compacted tail

    @property
    def ccap(self) -> int:
        return self.cand_cap or self.ef

    @property
    def steps(self) -> int:
        return self.max_steps or 8 * self.ef

    def stage_sizes(self, b: int) -> tuple[int, ...]:
        """The static lane-count ladder the traversal runs through for a
        batch of ``b`` queries: full width first, then repeated halvings
        while the next stage still holds >= ``lane_compact`` lanes.  One
        entry -> no compaction (the pre-compaction behavior)."""
        sizes = [b]
        if self.lane_compact > 0:
            while sizes[-1] // 2 >= self.lane_compact:
                sizes.append(sizes[-1] // 2)
        return tuple(sizes)


# ---------------------------------------------------------------------------
# Graph array preparation (memoized)
# ---------------------------------------------------------------------------
_GRAPH_ARRAYS_CACHE: dict = {}
_GRAPH_ARRAYS_CAP = 8


def graph_arrays(index: HnswIndex, attrs: F.AttributeTable,
                 version: int = 0) -> dict:
    """Flatten an HnswIndex + attribute table to the device array dict the
    production search (and the dry-run input_specs) consume.

    Memoized per ``(index identity, attrs identity, version)``: repeated
    FavorIndex / ServeEngine construction over the same built index (the
    benchmark-cache pattern) reuses the device arrays instead of re-uploading
    the corpus.  Entries die with their index/attrs (weakrefs, identity
    checked on hit so recycled ``id()``s never alias) and the cache is
    bounded.  Treat the returned dict as immutable -- copy before adding
    keys (FavorIndex does, for the quantized-scorer arrays).
    """
    key = (id(index), id(attrs), int(version))
    hit = _GRAPH_ARRAYS_CACHE.get(key)
    if hit is not None:
        iref, aref, g = hit
        if iref() is index and aref() is attrs:
            return g
        del _GRAPH_ARRAYS_CACHE[key]

    def _evict(k=key):
        _GRAPH_ARRAYS_CACHE.pop(k, None)

    upper = (np.stack(index.levels[1:], axis=0) if index.max_level >= 1
             else np.zeros((0, index.n, index.params.M), np.int32))
    g = {
        "vectors": jnp.asarray(index.vectors),
        "norms": jnp.asarray(index.norms.astype(np.float32)),
        "neighbors0": jnp.asarray(index.levels[0]),
        "upper": jnp.asarray(upper),
        "entry": jnp.asarray(index.entry_point, jnp.int32),
        "attrs_int": jnp.asarray(attrs.ints),
        "attrs_float": jnp.asarray(attrs.floats),
    }
    while len(_GRAPH_ARRAYS_CACHE) >= _GRAPH_ARRAYS_CAP:
        _GRAPH_ARRAYS_CACHE.pop(next(iter(_GRAPH_ARRAYS_CACHE)))
    # finalizers evict the entry the moment index/attrs die, so the cache
    # never pins device arrays of freed corpora (the hit-time identity
    # check above covers id() reuse in the window before GC runs)
    _GRAPH_ARRAYS_CACHE[key] = (weakref.ref(index), weakref.ref(attrs), g)
    weakref.finalize(index, _evict)
    weakref.finalize(attrs, _evict)
    return g


# which graph_arrays keys each epoch component owns: a refresh re-uploads
# only the keys of the components that actually changed
_COMPONENT_KEYS = {
    "vectors": ("vectors", "norms"),
    "graph": ("neighbors0", "upper", "entry"),
    "attributes": ("attrs_int", "attrs_float"),
}


def refresh_graph_arrays(index: HnswIndex, attrs: F.AttributeTable,
                         *, base: dict, changed: tuple[str, ...],
                         version: int) -> dict:
    """Incremental re-memoization after a mutation: build the dict for the
    new ``version`` by REUSING the device arrays of every component not in
    ``changed`` from ``base`` (the previous graph_arrays dict) and uploading
    only what moved.  A delete-only mutation, for example, re-uploads
    *nothing* here -- the tombstone mask is a separate ``alive`` key the
    caller overlays.  Extra keys on ``base`` (scorer codes, alive) are the
    caller's to carry; this handles the canonical seven only.
    """
    for c in changed:
        if c not in _COMPONENT_KEYS:
            raise ValueError(f"unknown component {c!r}; "
                             f"expected one of {tuple(_COMPONENT_KEYS)}")
    key = (id(index), id(attrs), int(version))
    hit = _GRAPH_ARRAYS_CACHE.get(key)
    if hit is not None:
        iref, aref, g = hit
        if iref() is index and aref() is attrs:
            return g
        del _GRAPH_ARRAYS_CACHE[key]

    def _evict(k=key):
        _GRAPH_ARRAYS_CACHE.pop(k, None)

    g = {k: base[k] for ks in _COMPONENT_KEYS.values() for k in ks}
    if "vectors" in changed:
        g["vectors"] = jnp.asarray(index.vectors)
        g["norms"] = jnp.asarray(index.norms.astype(np.float32))
    if "graph" in changed:
        upper = (np.stack(index.levels[1:], axis=0) if index.max_level >= 1
                 else np.zeros((0, index.n, index.params.M), np.int32))
        g["neighbors0"] = jnp.asarray(index.levels[0])
        g["upper"] = jnp.asarray(upper)
        g["entry"] = jnp.asarray(index.entry_point, jnp.int32)
    if "attributes" in changed:
        g["attrs_int"] = jnp.asarray(attrs.ints)
        g["attrs_float"] = jnp.asarray(attrs.floats)
    while len(_GRAPH_ARRAYS_CACHE) >= _GRAPH_ARRAYS_CAP:
        _GRAPH_ARRAYS_CACHE.pop(next(iter(_GRAPH_ARRAYS_CACHE)))
    _GRAPH_ARRAYS_CACHE[key] = (weakref.ref(index), weakref.ref(attrs), g)
    weakref.finalize(index, _evict)
    weakref.finalize(attrs, _evict)
    return g


# ---------------------------------------------------------------------------
# Packed visited set: (B, ceil(N/32)) uint32 bitfield
# ---------------------------------------------------------------------------
def _visited_words(n: int) -> int:
    return (n + 31) // 32


def _seen_bits(visited, rows, safe):
    """(B, W) words, (B, M) clamped ids -> (B, M) bool already-visited."""
    word = visited[rows[:, None], safe >> 5]
    return ((word >> (safe & 31).astype(jnp.uint32)) & 1) > 0


def _visit_bits(visited, rows, safe, mark):
    """Set the bits for ``mark``-ed entries of ``safe``.

    The scatter is an *add* (JAX has no scatter-or), which is exact only if
    every bit lands at most once -- so duplicates of an id **within one
    block** are dropped from the scatter first.  ``mark`` itself is left
    untouched for pool admission, preserving the old bool-bitmap semantics
    (``.at[].max`` was idempotent) bit for bit.
    """
    m = safe.shape[1]
    col = jnp.arange(m)
    dup = ((safe[:, :, None] == safe[:, None, :])
           & mark[:, :, None] & mark[:, None, :]
           & (col[None, None, :] < col[None, :, None]))
    first = mark & ~jnp.any(dup, axis=2)
    bits = jnp.where(first,
                     jnp.uint32(1) << (safe & 31).astype(jnp.uint32),
                     jnp.uint32(0))
    return visited.at[rows[:, None], safe >> 5].add(bits)


# ---------------------------------------------------------------------------
# Traversal building blocks
# ---------------------------------------------------------------------------
def _descend(g: dict, queries: jnp.ndarray, scorer, sstate: dict) -> jnp.ndarray:
    """Upper-layer greedy descent (no filtering), returns entry ids (B,)."""
    B = queries.shape[0]
    cur = jnp.full((B,), g["entry"], jnp.int32)
    curd = scorer.score_block(g, sstate, cur[:, None])[:, 0]
    n_upper = g["upper"].shape[0]
    for li in range(n_upper - 1, -1, -1):
        level = g["upper"][li]

        def cond(state):
            _, _, moved = state
            return jnp.any(moved)

        def body(state):
            cur, curd, moved = state
            nbrs = level[cur]                      # (B, M)
            ok = nbrs >= 0
            safe = jnp.maximum(nbrs, 0)
            d = scorer.score_block(g, sstate, safe)
            d = jnp.where(ok, d, INF)
            j = jnp.argmin(d, axis=1)
            best = jnp.take_along_axis(d, j[:, None], axis=1)[:, 0]
            better = moved & (best < curd)
            new_cur = jnp.where(better, jnp.take_along_axis(safe, j[:, None], axis=1)[:, 0], cur)
            new_d = jnp.where(better, best, curd)
            return new_cur, new_d, better

        cur, curd, _ = jax.lax.while_loop(
            cond, body, (cur, curd, jnp.ones((B,), bool)))
    return cur


def _merge_pool(pool: tuple, new: tuple, cap: int) -> tuple:
    """Merge (B, cap) pools with (B, M) new entries, keep the best ``cap``.

    ``pool`` and ``new`` are matching tuples of arrays, distances first,
    then payloads (ids, flags); ineligible new entries must carry d=+inf.
    The result is the stable ascending sort of ``pool || new`` by distance,
    cut to ``cap`` -- what ``argsort`` + ``take_along_axis`` give -- built
    with neither a sort nor a gather (both slow per row on TPU): an
    element's rank is the count of elements that sort before it (smaller,
    or equal and earlier), a (B, L, L) compare and sum, and output slot
    ``p`` selects the one element of rank ``p``, a (B, L, cap) one-hot
    select and sum.  Numbers pass the select as their int32 bits, so every
    value comes out bit for bit, ``+inf`` payloads included.  Distances are
    never NaN (a NaN would share a rank).
    """
    cols = [jnp.concatenate([p, n], axis=1) for p, n in zip(pool, new)]
    d = cols[0]
    pos = jnp.arange(d.shape[1])
    dj, di = d[:, :, None], d[:, None, :]
    before = (dj < di) | ((dj == di) & (pos[:, None] < pos[None, :]))
    rank = jnp.sum(before, axis=1, dtype=jnp.int32)          # (B, L)
    hit = rank[:, :, None] == jnp.arange(cap)                # (B, L, cap)

    def select(x):
        if x.dtype == jnp.bool_:
            return jnp.any(hit & x[:, :, None], axis=1)
        bits = jax.lax.bitcast_convert_type(x, jnp.int32)
        out = jnp.sum(jnp.where(hit, bits[:, :, None], 0), axis=1)
        return jax.lax.bitcast_convert_type(out, x.dtype)

    return tuple(select(x) for x in cols)


def _graph_traverse(g: dict, queries: jnp.ndarray, programs: dict,
                    D: jnp.ndarray, cfg: SearchConfig, scorer, valid,
                    *, rsf: bool) -> dict:
    """The ONE traversal body behind favor_graph_search / rsf_graph_search.

    ``scorer`` supplies the (approximate or exact) distances; the exclusion
    select, the validity-mask plumbing, the pools and the diagnostics are
    identical across scorers and across the FAVOR/RSF modes.  ``rsf=True``
    is the Result-Set-Filtering baseline: callers pass D = 0, R admits only
    TD rows, and the section-5.4 pbar guard is off (the baseline has no
    exclusion statistics to guard with).
    """
    B, _ = queries.shape
    N = g["vectors"].shape[0]
    ef, ccap = cfg.ef, cfg.ccap
    rows = jnp.arange(B)

    # optional live-index tombstone mask (N,) bool: dead nodes stay routable
    # (their edges still carry the walk) but are never admitted to R -- the
    # key is absent until the first delete, so static indexes trace the
    # exact pre-live program and stay bit-identical
    alive = g.get("alive")

    # Device scopes at the traversal's seams (HLO op metadata, trace-time
    # only; results are unchanged): ``graph.init`` (descent, pools, visited
    # set), per wave ``wave.select`` / ``wave.visit`` / ``wave.score`` /
    # ``wave.filter`` / ``wave.merge``, and ``graph.compact`` (the lane
    # ladder's gathers and scatters).  A device trace attributes the
    # traversal's time to them; none contains ``favor.``, which names the
    # Pallas kernels, each of which stays the innermost scope of its call.
    with jax.named_scope("graph.init"):
        sstate = scorer.prepare(g, queries, programs)
        ep = _descend(g, queries, scorer, sstate)        # (B,)

        # --- init pools with the entry point -------------------------------
        ep_d = scorer.score_block(g, sstate, ep[:, None])[:, 0]
        ep_td = F.eval_program_gathered(
            programs, g["attrs_int"][ep][:, None, :],
            g["attrs_float"][ep][:, None, :], xp=jnp)[:, 0]
        if alive is not None:
            ep_td = ep_td & alive[ep]
        ep_key = exclusion_compose(ep_d, ep_td, D)   # rsf: D = 0 -> plain d
        seed_ok = ep_td if rsf else jnp.ones((B,), bool)

        cand_d = jnp.full((B, ccap), INF).at[:, 0].set(ep_key)
        cand_i = jnp.full((B, ccap), -1, jnp.int32).at[:, 0].set(ep)
        res_d = jnp.full((B, ef), INF).at[:, 0].set(
            jnp.where(seed_ok, ep_key, INF))
        res_i = jnp.full((B, ef), -1, jnp.int32).at[:, 0].set(
            jnp.where(seed_ok, ep, -1))
        res_t = jnp.zeros((B, ef), bool).at[:, 0].set(ep_td)
        visited = jnp.zeros((B, _visited_words(N)), jnp.uint32).at[
            rows, ep >> 5].add(jnp.uint32(1) << (ep & 31).astype(jnp.uint32))
        active = (jnp.ones((B,), bool) if valid is None
                  else jnp.asarray(valid, bool))
        hops = jnp.zeros((B,), jnp.int32)
        path_td = jnp.zeros((B,), jnp.int32)

    def stage_loop(state, programs, D, sstate, limit: int):
        """One while_loop over the (possibly compacted) lane set.

        ``limit > 0`` adds the compaction exit: the loop also stops once the
        active-lane population fits the next (half-width) stage, so the
        caller can gather the survivors into a narrower batch.  Every op in
        the body is row-wise (argmin/merge/gather per lane) and every scorer
        is bit-stable across batch sizes (see ``pairwise_dist``), so lanes
        produce identical trajectories whichever stage width carries them.
        """
        S = state["active"].shape[0]
        rows = jnp.arange(S)

        def cond(s):
            go = jnp.any(s["active"]) & (s["step"] < cfg.steps)
            if limit > 0:
                go = go & (jnp.sum(s["active"]) > limit)
            return go

        def body(s):
            cand_d, cand_i = s["cand_d"], s["cand_i"]
            res_d, res_i, res_t = s["res_d"], s["res_i"], s["res_t"]
            active = s["active"]

            with jax.named_scope("wave.select"):
                # -- extract argmin of C (Algorithm 3 line 6) ----------------
                j = jnp.argmin(cand_d, axis=1)
                da = cand_d[rows, j]
                va = cand_i[rows, j]
                cand_d = jnp.where(active[:, None],
                                   cand_d.at[rows, j].set(INF), cand_d)

                # -- termination (line 8, with section 5.4 guard) ------------
                worst = jnp.max(res_d, axis=1)       # +inf while R not full
                full = jnp.isfinite(worst)
                plain_term = (da > cfg.gamma * worst) & full
                if rsf:
                    guard_ok = jnp.ones((S,), bool)
                else:
                    n_valid = jnp.sum(jnp.isfinite(res_d), axis=1)
                    n_td = jnp.sum(res_t & jnp.isfinite(res_d), axis=1)
                    pbar = n_td / jnp.maximum(n_valid, 1)
                    guard_ok = (cfg.pbar_min <= 0.0) | (pbar > cfg.pbar_min)
                terminate = plain_term & guard_ok
                exhausted = ~jnp.isfinite(da)
                new_active = active & ~terminate & ~exhausted
                expand = new_active                  # lanes that expand v_a

            with jax.named_scope("wave.visit"):
                # -- gather the neighbor block, mark it visited --------------
                va_safe = jnp.maximum(va, 0)
                nbrs = jnp.where(expand[:, None], g["neighbors0"][va_safe],
                                 -1)                 # (S, M0)
                ok = nbrs >= 0
                safe = jnp.maximum(nbrs, 0)
                seen = _seen_bits(s["visited"], rows, safe)
                new = ok & ~seen
                visited = _visit_bits(s["visited"], rows, safe, new)

            with jax.named_scope("wave.score"):
                d = scorer.score_block(g, sstate, safe)

            with jax.named_scope("wave.filter"):
                td = F.eval_program_gathered(
                    programs, g["attrs_int"][safe], g["attrs_float"][safe],
                    xp=jnp)
                if alive is not None:
                    td = td & alive[safe]
                key = exclusion_compose(d, td, D[:, None])   # Eq. 2

            with jax.named_scope("wave.merge"):
                # -- pool insertion (lines 15-24) ----------------------------
                worst_now = jnp.max(res_d, axis=1)   # +inf when R not full
                eligible = new & (key < worst_now[:, None])
                res_ok = (eligible & td) if rsf else eligible
                res_d, res_i, res_t = _merge_pool(
                    (res_d, res_i, res_t),
                    (jnp.where(res_ok, key, INF), jnp.where(res_ok, nbrs, -1),
                     td & res_ok), ef)
                cand_d, cand_i = _merge_pool(
                    (cand_d, cand_i),
                    (jnp.where(eligible, key, INF),
                     jnp.where(eligible, nbrs, -1)), ccap)

            with jax.named_scope("wave.filter"):
                va_td = F.eval_program_gathered(
                    programs, g["attrs_int"][va_safe][:, None, :],
                    g["attrs_float"][va_safe][:, None, :], xp=jnp)[:, 0]
                if alive is not None:
                    va_td = va_td & alive[va_safe]
            return {
                "cand_d": cand_d, "cand_i": cand_i,
                "res_d": res_d, "res_i": res_i, "res_t": res_t,
                "visited": visited, "active": new_active,
                "step": s["step"] + 1,
                "hops": s["hops"] + expand.astype(jnp.int32),
                "path_td": s["path_td"] + (expand & va_td).astype(jnp.int32),
            }

        return jax.lax.while_loop(cond, body, state)

    state = {
        "cand_d": cand_d, "cand_i": cand_i,
        "res_d": res_d, "res_i": res_i, "res_t": res_t,
        "visited": visited, "active": active,
        "step": jnp.asarray(0, jnp.int32), "hops": hops, "path_td": path_td,
    }

    # --- lane-compacted traversal: a static ladder of stage widths ----------
    # The full-width loop exits as soon as the active-lane population fits
    # half the batch; survivors are packed (active-first, original order --
    # a stable argsort on the inactive flag) into the next stage and the
    # finished lanes' pools are scattered back into the full-width buffers.
    # A padded bucket (or a long straggler tail) therefore stops paying
    # B-wide waves the moment most lanes are done, instead of running every
    # wave at the width of the slowest lane.  Each stage is one more traced
    # while_loop inside the SAME jitted executable, so the compiled-shape
    # count per bucket is unchanged (the CI compile guard asserts this).
    sizes = cfg.stage_sizes(B)
    out_keys = ("res_d", "res_i", "res_t", "hops", "path_td")
    final = {k: state[k] for k in out_keys}
    perm = jnp.arange(B)
    progs_s, D_s, sstate_s = programs, D, sstate
    for si, S in enumerate(sizes):
        limit = sizes[si + 1] if si + 1 < len(sizes) else 0
        state = stage_loop(state, progs_s, D_s, sstate_s, limit)
        if len(sizes) == 1:
            final = {k: state[k] for k in out_keys}
            break
        with jax.named_scope("graph.compact"):
            final = {k: final[k].at[perm].set(state[k]) for k in out_keys}
            if si + 1 < len(sizes):
                nxt = sizes[si + 1]
                sel = jnp.argsort(~state["active"], stable=True)[:nxt]
                perm = perm[sel]
                state = {k: (v if k == "step" else v[sel])
                         for k, v in state.items()}
                progs_s = {k: v[sel] for k, v in progs_s.items()}
                D_s = D_s[sel]
                # scorer state is per-query EXCEPT the keys the scorer
                # declares shared (e.g. SqScorer's query-independent
                # quadratic weights) -- those must not be lane-sliced
                shared = getattr(scorer, "shared_state", ())
                sstate_s = {
                    k: (v if k in shared
                        else jax.tree_util.tree_map(lambda a: a[sel], v))
                    for k, v in sstate_s.items()}
    waves = state["step"]
    state = final

    # --- final S: k nearest TD in R (Algorithm 2 line 9) --------------------
    sd = jnp.where(state["res_t"], state["res_d"], INF)  # TD dbar == scorer dist
    if scorer.exact:
        order = jnp.argsort(sd, axis=1)[:, : cfg.k]
        out_d = jnp.take_along_axis(sd, order, axis=1)
        out_i = jnp.take_along_axis(state["res_i"], order, axis=1)
        out_i = jnp.where(jnp.isfinite(out_d), out_i, -1)
        if valid is not None:
            vmask = jnp.asarray(valid, bool)[:, None]
            out_i = jnp.where(vmask, out_i, -1)
            out_d = jnp.where(vmask, out_d, INF)
    else:
        # quantized scorer: the pool holds approximate distances -- exact
        # f32 re-rank of the top-R TD candidates, exactly like the brute
        # route's ADC scan (quant/adc.py); R caps at ef (the pool size)
        from ..quant.adc import _exact_rerank
        r = min(ef, max(cfg.k, cfg.graph_rerank * cfg.k))
        order = jnp.argsort(sd, axis=1)[:, :r]
        cand = jnp.take_along_axis(state["res_i"], order, axis=1)
        cand = jnp.where(jnp.isfinite(
            jnp.take_along_axis(sd, order, axis=1)), cand, -1)
        out_i, out_d = _exact_rerank(g["vectors"], g["norms"], queries,
                                     cand, k=cfg.k, valid=valid)
        if valid is not None:
            out_i = jnp.where(jnp.asarray(valid, bool)[:, None], out_i, -1)
    return {"ids": out_i, "dists": out_d,
            "hops": state["hops"], "path_td": state["path_td"],
            # broadcast: a wave is a batch-wide event (every co-resident lane
            # pays it), so each query reports the ladder's total wave count
            "waves": jnp.broadcast_to(waves, state["hops"].shape)}


# ---------------------------------------------------------------------------
# Public entry points (thin wrappers over the shared body)
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("cfg",))
def favor_graph_search(g: dict, queries: jnp.ndarray, programs: dict,
                       D: jnp.ndarray, cfg: SearchConfig,
                       valid=None) -> dict:
    """Batched OptiGreedySearch (Algorithm 3) with exclusion distances.

    g         : graph_arrays dict (possibly one shard of the DB); for
                ``cfg.graph_quant`` it must also carry the scorer arrays
                (codes + centroids | sq_lo/sq_scale)
    queries   : (B, d) float32
    programs  : batched filter programs {valid (B,W), imask, flo, fhi}
    D         : (B,) per-query exclusion distance (Eq. 14, from p_hat)
    valid     : optional (B,) bool lane mask (bucket padding): False lanes
                start inactive -- they never expand a node, cost no search
                work, and return ids=-1 / dists=+inf / hops=0
    returns   : {"ids": (B,k) int32 (-1 pad), "dists": (B,k) f32 (+inf pad),
                 "hops": (B,), "path_td": (B,), "waves": (B,) int32 -- total
                 while_loop iterations across the compaction stage ladder
                 (batch-wide, so identical for every lane of the batch)}
    """
    return _graph_traverse(g, queries, programs, D, cfg, scorer_for(cfg),
                           valid, rsf=False)


@partial(jax.jit, static_argnames=("cfg",))
def rsf_graph_search(g: dict, queries: jnp.ndarray, programs: dict,
                     cfg: SearchConfig, valid=None) -> dict:
    """Result-Set-Filtering baseline on the same machinery: D = 0 and R only
    admits TD (C takes everything) -- used by benchmarks for head-to-head
    QPS/recall under identical batching.  Same lane-mask contract and
    hops/path_td diagnostics as favor_graph_search (one traversal body)."""
    B = queries.shape[0]
    return _graph_traverse(g, queries, programs,
                           jnp.zeros((B,), jnp.float32), cfg,
                           scorer_for(cfg), valid, rsf=True)
