"""FavorIndex: the end-to-end FAVOR API (paper Figure 1 workflow).

Offline:  build a conventional HNSW over the vectors, record Delta_d (Eq. 5),
          draw the selectivity sample, attach the attribute table.
Online :  compile each query's filter to a DNF program, estimate p_hat on the
          sample (section 4.2), route by lambda (section 4.1), compute the
          exclusion distance D(p_hat) (Eq. 14) and execute either the PreFBF
          scan or the exclusion-distance graph search (section 5), returning
          the k nearest target points.

The online pipeline itself lives in router.execute (shared with the serving
engine and the sharded backend); this class owns offline state -- device
arrays, selectivity sample, optional PQ/SQ codes -- and exposes it through a
LocalBackend.  ``query(queries, filters, SearchOptions(...))`` is the
search API.
"""
from __future__ import annotations

import os
import time
import warnings
from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np

from . import filters as F
from . import prefbf, selectivity
from .hnsw import HnswIndex, HnswParams, build_hnsw
from .options import BuildSpec, QuantSpec, SearchOptions
from .router import SearchResult, execute
from .search import graph_arrays, refresh_graph_arrays
from ..index.epochs import ComponentEpochs
from ..index.live import LiveState

__all__ = ["FavorIndex", "SearchResult"]


@dataclass
class _MergePrep:
    """Everything ``merge_prepare`` built off the serving path, ready for an
    atomic ``merge_commit`` swap.  ``graph_epoch`` guards staleness."""
    from_slot: int
    n_live: int
    graph_epoch: int
    index: HnswIndex
    attrs: "F.AttributeTable"
    chunk: int
    pv: object
    pn0: object
    pi: object
    pf: object
    codes: object
    g: dict


class FavorIndex:
    """Single-host FAVOR index.  Execution goes through a LocalBackend; the
    multi-device variant is core.backend.ShardedBackend behind the same
    Backend protocol (and the same ServeEngine)."""

    def __init__(self, index: HnswIndex, attrs: F.AttributeTable,
                 spec: BuildSpec | None = None, *, codebook=None,
                 codes=None):
        if spec is not None and not isinstance(spec, BuildSpec):
            raise TypeError("spec must be a BuildSpec, got "
                            f"{type(spec).__name__}")
        if spec is None:
            spec = BuildSpec()
        # an externally trained/loaded codebook implies its quant kind AND
        # geometry: derive the spec from the codebook so fi.spec faithfully
        # describes the memory format actually in use (reusable for e.g.
        # ShardedBackend.build parity)
        if spec.quant is None and codebook is not None:
            from ..quant import PQCodebook
            if isinstance(codebook, PQCodebook):
                q = QuantSpec(kind="pq", m=codebook.m, nbits=codebook.nbits)
            else:
                q = QuantSpec(kind="sq")
            spec = BuildSpec(hnsw=spec.hnsw, selector=spec.selector,
                             prefbf_chunk=spec.prefbf_chunk, quant=q)

        self.spec = spec
        self.index = index
        self.attrs = attrs
        self.sel_cfg = spec.selector
        self.schema = attrs.schema
        # memoized per (index, attrs): rebuilding a FavorIndex over the same
        # built HNSW (benchmark cache, test fixtures) reuses device arrays;
        # copy so the quantized-scorer keys below never touch the cache
        self.g = dict(graph_arrays(index, attrs))

        samp = selectivity.sample_indices(
            index.n, self.sel_cfg.sample_rate, self.sel_cfg.min_sample,
            self.sel_cfg.max_sample, seed=index.params.seed + 17)
        self.sample_idx = samp
        self.sample_ints = jnp.asarray(attrs.ints[samp])
        self.sample_floats = jnp.asarray(attrs.floats[samp])

        self.prefbf_chunk = min(spec.prefbf_chunk, max(256, index.n))
        pv, pn, pi, pf = prefbf.pad_db(index.vectors,
                                       index.norms.astype(np.float32),
                                       attrs.ints, attrs.floats,
                                       self.prefbf_chunk)
        self._pf = (jnp.asarray(pv), jnp.asarray(pn), jnp.asarray(pi),
                    jnp.asarray(pf))
        # pristine padded norms, kept so tombstones can be (re)masked onto
        # the scan arrays without re-reading the host copy
        self._pn0 = self._pf[1]

        # -- live mutation state (index subsystem) ----------------------------
        self.epochs = ComponentEpochs()
        self.live: LiveState | None = None
        self._alive: np.ndarray | None = None   # base-row tombstone mask

        # -- optional compressed-domain scan state (quant subsystem) ---------
        q = spec.quant
        if q is not None and codebook is not None:
            from ..quant import PQCodebook
            cb_kind = "pq" if isinstance(codebook, PQCodebook) else "sq"
            if cb_kind != q.kind:
                raise ValueError(f"spec.quant.kind={q.kind!r} does not match "
                                 f"the supplied {cb_kind!r} codebook")
            if cb_kind == "pq" and (codebook.m, codebook.nbits) != (q.m, q.nbits):
                raise ValueError(
                    f"spec.quant geometry (m={q.m}, nbits={q.nbits}) does not "
                    f"match the supplied codebook (m={codebook.m}, "
                    f"nbits={codebook.nbits})")
        self.quantize = q.kind if q is not None else None
        self.rerank = q.rerank if q is not None else 4
        self.codebook = codebook
        self._codes = None
        self._cb_dev = None
        self._backend = None
        if codes is not None and q is None:
            raise ValueError("codes= supplied but the index requests no "
                             "quantization (spec.quant is None and no "
                             "codebook was given)")
        if q is not None:
            from .. import quant
            if codebook is None:
                if index.n == 0:
                    raise ValueError(
                        "cannot train a codebook on an empty index; pass "
                        "codebook= (or build unquantized and re-quantize "
                        "after the first merge)")
                if q.kind == "pq":
                    codebook = quant.train_pq(
                        index.vectors, m=q.m, nbits=q.nbits,
                        iters=q.train_iters, sample=q.train_sample,
                        seed=index.params.seed)
                else:
                    codebook = quant.train_sq(index.vectors)
            self.codebook = codebook
            # encode the *padded* DB so code rows align with the _pf arrays
            # (padded rows encode the zero vector; their +inf norms gate them
            # out of the compressed scan)
            if codes is not None:
                codes = np.asarray(codes)
                if codes.shape[0] != index.n:
                    raise ValueError(f"codes= carries {codes.shape[0]} rows "
                                     f"for an index of {index.n}")
                pad = pv.shape[0] - index.n
                if pad:
                    codes = np.concatenate([
                        codes, quant.encode(
                            codebook, np.zeros((pad, index.dim), np.float32))])
                self._codes = jnp.asarray(codes)
            else:
                self._codes = jnp.asarray(quant.encode(codebook, pv))
            if q.kind == "pq":
                self._cb_dev = (jnp.asarray(codebook.centroids),)
            else:
                self._cb_dev = (jnp.asarray(codebook.lo),
                                jnp.asarray(codebook.scale))
            self._attach_scorer_arrays()

    # -- construction --------------------------------------------------------
    @staticmethod
    def build(vectors: np.ndarray, attrs: F.AttributeTable,
              params: HnswParams | None = None,
              spec: BuildSpec | None = None, **kw) -> "FavorIndex":
        if spec is not None and spec.hnsw is not None:
            if params is not None:
                raise ValueError("pass HNSW params via either params= or "
                                 "spec.hnsw, not both")
            params = spec.hnsw
        t0 = time.perf_counter()
        index = build_hnsw(vectors, params)
        build_s = time.perf_counter() - t0
        fi = FavorIndex(index, attrs, spec, **kw)
        fi.build_seconds = build_s
        return fi

    @property
    def delta_d(self) -> float:
        return self.index.delta_d

    def _attach_scorer_arrays(self) -> None:
        """Graph-route scorer arrays (core.scoring): code rows 0..N-1 of the
        padded encoding align with the graph arrays (pad_db appends), so the
        traversal can score on codes via SearchOptions.graph_quant."""
        if self._codes is None:
            return
        self.g["codes"] = self._codes[: self.index.n]
        if self.quantize == "pq":
            self.g["centroids"] = self._cb_dev[0]
        else:
            self.g["sq_lo"], self.g["sq_scale"] = self._cb_dev

    def version(self) -> int:
        """Aggregate data epoch consumed by layered caches
        (Backend.version): any component bump changes it."""
        return self.epochs.total

    def versions(self) -> dict:
        """Scoped epochs (vectors / attributes / graph) for caches that
        invalidate per component instead of dropping everything."""
        return self.epochs.as_dict()

    def bump_version(self, components: tuple[str, ...] | None = None) -> int:
        """Mark served rows as changed (rebuild, attribute update):
        CachingBackend wrappers invalidate on the next call and the memoized
        graph arrays are re-uploaded under the new epoch (an in-place attrs
        edit would otherwise keep serving the stale device copies).

        ``components`` (subset of vectors/attributes/graph) scopes the bump:
        only the named components' device arrays are re-uploaded (the rest
        are reused from the current dict) and only their epochs move.  None
        keeps the legacy bump-everything behavior.
        """
        if components is None:
            self.epochs.bump_all()
            self.g = dict(graph_arrays(self.index, self.attrs,
                                       version=self.epochs.total))
        else:
            self.epochs.bump(*components)
            self.g = dict(refresh_graph_arrays(
                self.index, self.attrs, base=self.g,
                changed=tuple(components), version=self.epochs.total))
        self._attach_scorer_arrays()
        if self._alive is not None:
            self.g["alive"] = jnp.asarray(self._alive)
        return self.epochs.total

    # -- live mutation API (index subsystem) ----------------------------------
    def _ensure_live(self) -> LiveState:
        if self.live is None:
            self.live = LiveState(self.index.n, self.index.dim,
                                  self.attrs.ints.shape[1],
                                  self.attrs.floats.shape[1])
        return self.live

    def _apply_tombstones(self, dead_rows: np.ndarray) -> None:
        """Thread newly-dead base rows onto the device arrays: an ``alive``
        key for the graph traversal and +inf norms for every brute scan.
        Nothing else re-uploads -- vectors/neighbors/attrs stay put."""
        if len(dead_rows) == 0:
            return
        alive = self.live.base_alive
        self._alive = alive
        self.g["alive"] = jnp.asarray(alive)
        pad = self._pn0.shape[0] - self.index.n
        alive_pad = np.concatenate([alive, np.ones((pad,), bool)])
        self._pf = (self._pf[0],
                    jnp.where(jnp.asarray(alive_pad), self._pn0, jnp.inf),
                    self._pf[2], self._pf[3])

    def upsert(self, vectors: np.ndarray, ints=None, floats=None, *,
               replace=None) -> np.ndarray:
        """Stream rows into the live delta; returns their ids (positional:
        ``base_n + slot``).  ``replace=`` retires the named ids first (an
        update is delete + fresh insert; the new ids are the handles)."""
        live = self._ensure_live()
        ids, dead = live.upsert(vectors, ints, floats, replace=replace)
        self._apply_tombstones(dead)
        self.epochs.bump("vectors")
        return ids

    def delete(self, ids) -> int:
        """Tombstone ids (base rows or unmerged delta rows); returns how
        many were found alive."""
        live = self._ensure_live()
        n, dead = live.delete(ids)
        self._apply_tombstones(dead)
        if n:
            self.epochs.bump("vectors")
        return n

    def live_view(self):
        return None if self.live is None else self.live.view()

    def live_stats(self) -> dict:
        if self.live is None:
            return {"base_rows": self.index.n, "dead_base_rows": 0,
                    "delta_rows": 0, "delta_slots": 0, "upserts": 0,
                    "deletes": 0, "replaced": 0, "missing_deletes": 0}
        return self.live.stats()

    def merge_prepare(self, *, wave: int = 512,
                      on_wave=None) -> "_MergePrep | None":
        """Phase 1 of a merge: snapshot the delta and run the expensive work
        (bulk graph build, attribute concat, scan-array padding, code
        re-encode, device upload) WITHOUT mutating any served state.

        Safe to run off-thread while serving continues: the snapshot
        boundary is ``cnt = delta.count`` read *before* any array reference
        (append never rewrites rows below ``count`` and ``_grow`` reallocs,
        so rows ``[:cnt]`` of whatever arrays we then see are stable), and
        ``bulk_add`` builds into a fresh builder without touching the source
        index.  Returns None when there is nothing to merge.
        """
        from ..index.bulk import bulk_add
        live = self.live
        if live is None or live.delta.count == 0:
            return None
        d = live.delta
        cnt = int(d.count)       # snapshot boundary: read BEFORE array refs
        index, attrs = self.index, self.attrs
        vecs = d.vectors[:cnt].copy()
        ints = d.ints[:cnt].copy()
        flts = d.floats[:cnt].copy()
        link = d.alive[:cnt].copy()
        graph_epoch = self.epochs.graph
        new_index = bulk_add(index, vecs, wave=wave, link=link,
                             on_wave=on_wave)
        new_attrs = F.AttributeTable(
            self.schema,
            np.concatenate([attrs.ints, ints]),
            np.concatenate([attrs.floats, flts]))
        chunk = min(self.spec.prefbf_chunk, max(256, new_index.n))
        pv, pn, pi, pf = prefbf.pad_db(new_index.vectors,
                                       new_index.norms.astype(np.float32),
                                       new_attrs.ints, new_attrs.floats,
                                       chunk)
        codes = None
        if self.codebook is not None:
            from .. import quant
            codes = jnp.asarray(quant.encode(self.codebook, pv))
        # pre-upload the graph/scan arrays here (the slow part); the commit
        # assigns the dict directly instead of re-keying the memo
        g = dict(graph_arrays(new_index, new_attrs, version=0))
        return _MergePrep(
            from_slot=cnt, n_live=int(link.sum()), graph_epoch=graph_epoch,
            index=new_index, attrs=new_attrs, chunk=chunk,
            pv=jnp.asarray(pv), pn0=jnp.asarray(pn), pi=jnp.asarray(pi),
            pf=jnp.asarray(pf), codes=codes, g=g)

    def merge_commit(self, prep: "_MergePrep") -> dict | None:
        """Phase 2: atomic swap of the served state onto the prepared merge.

        Cheap (no device upload, no build) -- callers holding a serving lock
        can run it without a perceptible stall.  Mutations that landed since
        the snapshot are honored: deletes become tombstones on the fresh
        arrays (current ``live`` alive state wins over the snapshot's), and
        delta slots past the snapshot boundary carry into the new delta with
        their ids intact (positional-id discipline).  Returns None -- and
        changes nothing -- if the base graph was rebuilt since the snapshot
        (a competing merge or explicit rebuild), in which case the prepared
        state is stale and must be discarded.
        """
        live = self.live
        if live is None or self.epochs.graph != prep.graph_epoch:
            return None
        cnt = prep.from_slot
        base = (live.base_alive if live.base_alive is not None
                else np.ones((live.base_n,), bool))
        alive = np.concatenate([base, live.delta.alive[:cnt]])
        self._alive = None if alive.all() else alive
        self.index = prep.index
        self.attrs = prep.attrs
        self.prefbf_chunk = prep.chunk

        self._pn0 = prep.pn0
        pn = prep.pn0
        if self._alive is not None:
            pad = int(pn.shape[0]) - prep.index.n
            alive_pad = np.concatenate([self._alive, np.ones((pad,), bool)])
            pn = jnp.where(jnp.asarray(alive_pad), pn, jnp.inf)
        self._pf = (prep.pv, pn, prep.pi, prep.pf)
        self._codes = prep.codes

        # vectors (membership) and graph (base arrays rebuilt) move;
        # attributes deliberately do not -- the estimator sample is untouched
        self.epochs.bump("vectors", "graph")
        self.g = dict(prep.g)
        self._attach_scorer_arrays()
        if self._alive is not None:
            self.g["alive"] = jnp.asarray(self._alive)
        live.reset_after_merge(prep.index.n, self._alive, from_slot=cnt)
        return {"merged_slots": cnt, "merged_live": prep.n_live,
                "n": prep.index.n}

    def merge(self, *, wave: int = 512) -> dict:
        """Fold the delta segment into the base HNSW (device-parallel bulk
        build) and return to the static fast path.

        Every delta *slot* is appended in order -- dead slots ride along as
        tombstoned, unlinked rows -- so surviving ids keep their positions.
        The selectivity sample is intentionally left untouched: base rows
        keep their ids and their attributes, so the estimator (and any
        selectivity cache over it) stays warm across merges.

        Implemented as ``merge_prepare`` + ``merge_commit``; background
        callers run the two phases on different threads.
        """
        prep = self.merge_prepare(wave=wave)
        if prep is None:
            return {"merged_slots": 0, "merged_live": 0, "n": self.index.n}
        out = self.merge_commit(prep)
        if out is None:  # pragma: no cover - single-threaded epochs are stable
            raise RuntimeError("merge_commit rejected a same-thread prepare")
        return out

    @property
    def backend(self):
        """The LocalBackend view of this index (cached)."""
        if self._backend is None:
            from .backend import LocalBackend
            self._backend = LocalBackend(self)
        return self._backend

    # -- online search --------------------------------------------------------
    def query(self, queries: np.ndarray, filters,
              opts: SearchOptions | None = None) -> SearchResult:
        """Typed search API: one SearchOptions drives routing + execution
        (shared router; identical on every backend)."""
        return execute(self.backend, queries, filters, opts or SearchOptions())

    def bytes_per_vector(self, quantized: bool = False) -> int:
        """Bytes streamed per DB row by the brute scan (float32 vs codes)."""
        if quantized:
            if self.codebook is None:
                raise ValueError("index is not quantized")
            return self.codebook.bytes_per_vector()
        return 4 * self.index.dim

    # -- persistence -----------------------------------------------------------
    def _quant_payload(self) -> dict | None:
        """Quantization state persisted inside the .hnsw.npz: the codebook
        tables AND the encoded codes (unpadded), so a reloaded index serves
        use_pq / graph_quant without re-training or re-encoding."""
        if self.codebook is None:
            return None
        payload = {"kind": self.quantize, "dim": self.codebook.dim,
                   "codes": np.asarray(self._codes)[: self.index.n]}
        if self.quantize == "pq":
            payload["centroids"] = np.asarray(self.codebook.centroids)
        else:
            payload["lo"] = np.asarray(self.codebook.lo)
            payload["scale"] = np.asarray(self.codebook.scale)
        return payload

    def save(self, path: str) -> None:
        if self.live is not None and (self.live.delta.count
                                      or self.live.has_tombstones):
            warnings.warn(
                "FavorIndex.save: unmerged live mutations (delta rows or "
                "tombstones) are not persisted -- call merge() first",
                stacklevel=2)
        self.index.save(path + ".hnsw.npz", quant=self._quant_payload())
        np.savez_compressed(path + ".attrs.npz", ints=self.attrs.ints,
                            floats=self.attrs.floats,
                            kinds=np.array([c.kind for c in self.schema.columns]),
                            names=np.array([c.name for c in self.schema.columns]),
                            vocabs=np.array([c.vocab or 0 for c in self.schema.columns]))
        if self.codebook is not None:
            from ..quant import save_codebook
            save_codebook(path + ".quant.npz", self.codebook)

    @staticmethod
    def load(path: str, spec: BuildSpec | None = None, **kw) -> "FavorIndex":
        index = HnswIndex.load(path + ".hnsw.npz")
        z = np.load(path + ".attrs.npz")
        cols = tuple(
            F.ColumnSpec(str(n), str(k), int(v) if str(k) == "int" else None)
            for n, k, v in zip(z["names"], z["kinds"], z["vocabs"]))
        attrs = F.AttributeTable(F.Schema(cols), z["ints"], z["floats"])
        qs = index.quant_state
        if qs is not None and kw.get("codebook") is None:
            from ..quant import PQCodebook, SQCodebook
            if qs["kind"] == "pq":
                kw["codebook"] = PQCodebook(qs["centroids"], int(qs["dim"]))
            else:
                kw["codebook"] = SQCodebook(qs["lo"], qs["scale"],
                                            int(qs["dim"]))
            kw.setdefault("codes", qs["codes"])
        elif kw.get("codebook") is None:
            qpath = path + ".quant.npz"
            if os.path.exists(qpath):
                from ..quant import load_codebook
                kw["codebook"] = load_codebook(qpath)  # kind is inferred
            elif spec is not None and spec.quant is not None:
                raise ValueError(
                    f"spec requests quant kind={spec.quant.kind!r} but "
                    f"{path!r} was saved without quantization state; rebuild "
                    "with a QuantSpec or pass codebook= explicitly")
        qs_kind = qs["kind"] if qs is not None else None
        if (qs_kind is not None and spec is not None and spec.quant is not None
                and spec.quant.kind != qs_kind):
            raise ValueError(f"spec requests quant kind={spec.quant.kind!r} "
                             f"but the saved index carries {qs_kind!r}")
        return FavorIndex(index, attrs, spec, **kw)
