"""Product-quantization codebooks (JAX k-means) + scalar-quantization fallback.

PQ splits each d-dim vector into ``M`` contiguous subvectors of ``dsub``
dims (zero-padded when ``M`` does not divide ``d``) and learns one K=2^nbits
centroid codebook per subspace with Lloyd's algorithm, vmapped over
subspaces so all M k-means runs share the same compiled program.  A vector
is stored as M uint8 codes (nbits <= 8), i.e. ``M`` bytes instead of
``4 * d`` -- a 16x compression at the paper's 128-dim scale with M=32.

The scalar-quantization (SQ) fallback is per-dimension affine int8: 4x
compression, no training beyond a min/max pass, and trivially exact decode
arithmetic -- the safety net when a dataset is too small or too skewed for
k-means codebooks to converge well.

Both codebooks round-trip through a single npz (``save_codebook`` /
``load_codebook``) so FavorIndex persistence can carry them alongside the
HNSW arrays.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


@dataclass
class PQCodebook:
    """Per-subspace centroid tables.

    centroids : (M, K, dsub) float32
    dim       : original vector dimensionality (<= M * dsub; the tail of the
                last subspace is zero padding)
    """

    centroids: np.ndarray
    dim: int

    @property
    def m(self) -> int:
        return int(self.centroids.shape[0])

    @property
    def ksub(self) -> int:
        return int(self.centroids.shape[1])

    @property
    def dsub(self) -> int:
        return int(self.centroids.shape[2])

    @property
    def nbits(self) -> int:
        return int(round(float(np.log2(self.ksub))))

    @property
    def padded_dim(self) -> int:
        return self.m * self.dsub

    def bytes_per_vector(self) -> int:
        return self.m  # one uint8 code per subspace (nbits <= 8)


@dataclass
class SQCodebook:
    """Per-dimension affine int8 quantizer: x ~= code * scale + lo."""

    lo: np.ndarray     # (d,) float32
    scale: np.ndarray  # (d,) float32
    dim: int

    def bytes_per_vector(self) -> int:
        return self.dim  # one uint8 code per dimension

    @property
    def padded_dim(self) -> int:
        return self.dim


def _pad_split(x: np.ndarray | jnp.ndarray, m: int, dsub: int):
    """(N, d) -> (N, m, dsub) with zero padding on the feature tail."""
    n, d = x.shape
    pad = m * dsub - d
    if pad:
        x = jnp.concatenate(
            [jnp.asarray(x), jnp.zeros((n, pad), jnp.float32)], axis=1)
    return jnp.asarray(x).reshape(n, m, dsub)


# ---------------------------------------------------------------------------
# k-means (one subspace; vmapped over M)
# ---------------------------------------------------------------------------
def _assign(x, c):
    """(n, d), (k, d) -> (n,) nearest-centroid ids (squared L2).  The
    dots contract at f32 (on TPU the default precision would round both
    operands to bf16 and pick different centroids than the CPU does)."""
    d2 = (jnp.sum(x * x, axis=1)[:, None]
          - 2.0 * jnp.matmul(x, c.T, precision=HIGHEST)
          + jnp.sum(c * c, axis=1)[None, :])
    return jnp.argmin(d2, axis=1)


def _lloyd_step(c, x, k: int):
    a = _assign(x, c)
    oh = jax.nn.one_hot(a, k, dtype=jnp.float32)        # (n, k)
    cnt = jnp.sum(oh, axis=0)                            # (k,)
    sums = jnp.matmul(oh.T, x, precision=HIGHEST)        # (k, d) MXU
    # empty clusters keep their previous centroid (no respawn: deterministic)
    return jnp.where(cnt[:, None] > 0, sums / jnp.maximum(cnt, 1.0)[:, None], c)


@partial(jax.jit, static_argnames=("k", "iters"))
def _kmeans(x, key, *, k: int, iters: int):
    """x (n, d) -> centroids (k, d).  Init: k distinct sample rows."""
    idx = jax.random.choice(key, x.shape[0], (k,), replace=False)
    c0 = x[idx]
    c = jax.lax.fori_loop(0, iters, lambda _, c: _lloyd_step(c, x, k), c0)
    return c


def train_pq(vectors: np.ndarray, m: int = 8, nbits: int = 8, *,
             iters: int = 20, sample: int = 65536, seed: int = 0) -> PQCodebook:
    """Train an M x 2^nbits PQ codebook on (a sample of) the dataset."""
    assert 1 <= nbits <= 8, "codes are uint8: nbits must be in [1, 8]"
    n, d = vectors.shape
    k = 1 << nbits
    rng = np.random.default_rng(seed)
    if n > sample:
        rows = rng.choice(n, size=sample, replace=False)
        vectors = vectors[rows]
        n = sample
    assert n >= k, f"need >= {k} training vectors for 2^{nbits} centroids, got {n}"

    dsub = -(-d // m)
    xs = _pad_split(np.asarray(vectors, np.float32), m, dsub)  # (n, m, dsub)
    xs = jnp.transpose(xs, (1, 0, 2))                          # (m, n, dsub)
    keys = jax.random.split(jax.random.PRNGKey(seed), m)
    # one subspace at a time (lax.map, not vmap): on TPU v5e the vmapped
    # k-means and the vmapped 2^16-row assignment return wrong centroids and
    # codes, while the per-subspace program matches the CPU
    cents = jax.lax.map(lambda a: _kmeans(a[0], a[1], k=k, iters=iters),
                        (xs, keys))
    return PQCodebook(np.asarray(cents, np.float32), dim=d)


@jax.jit
def _encode_chunk(xs, centroids):
    """xs (n, m, dsub), centroids (m, k, dsub) -> codes (n, m) int32, one
    subspace at a time (see train_pq)."""
    codes = jax.lax.map(lambda a: _assign(*a),
                        (jnp.transpose(xs, (1, 0, 2)), centroids))
    return codes.T


def encode(cb: PQCodebook | SQCodebook, vectors: np.ndarray,
           chunk: int = 65536) -> np.ndarray:
    """Vectors (N, d) -> uint8 codes: (N, M) for PQ, (N, d) for SQ."""
    vectors = np.asarray(vectors, np.float32)
    if isinstance(cb, SQCodebook):
        q = np.rint((vectors - cb.lo[None, :]) / cb.scale[None, :])
        return np.clip(q, 0, 255).astype(np.uint8)
    cents = jnp.asarray(cb.centroids)
    out = np.empty((vectors.shape[0], cb.m), np.uint8)
    for s in range(0, vectors.shape[0], chunk):
        xs = _pad_split(vectors[s:s + chunk], cb.m, cb.dsub)
        out[s:s + chunk] = np.asarray(_encode_chunk(xs, cents), np.uint8)
    return out


def decode(cb: PQCodebook | SQCodebook, codes: np.ndarray) -> np.ndarray:
    """Codes -> approximate float32 vectors (N, dim)."""
    codes = np.asarray(codes)
    if isinstance(cb, SQCodebook):
        return codes.astype(np.float32) * cb.scale[None, :] + cb.lo[None, :]
    # gather (N, m, dsub) then flatten and drop the zero-padded tail
    recon = cb.centroids[np.arange(cb.m)[None, :], codes.astype(np.int64)]
    return recon.reshape(codes.shape[0], cb.padded_dim)[:, :cb.dim].copy()


def train_sq(vectors: np.ndarray) -> SQCodebook:
    """Per-dimension affine int8 quantizer from a min/max pass."""
    vectors = np.asarray(vectors, np.float32)
    lo = vectors.min(axis=0)
    hi = vectors.max(axis=0)
    scale = np.maximum((hi - lo) / 255.0, 1e-12).astype(np.float32)
    return SQCodebook(lo.astype(np.float32), scale, dim=vectors.shape[1])


# ---------------------------------------------------------------------------
# persistence
# ---------------------------------------------------------------------------
def save_codebook(path: str, cb: PQCodebook | SQCodebook) -> None:
    if isinstance(cb, PQCodebook):
        np.savez_compressed(path, kind="pq", centroids=cb.centroids,
                            dim=np.int64(cb.dim))
    else:
        np.savez_compressed(path, kind="sq", lo=cb.lo, scale=cb.scale,
                            dim=np.int64(cb.dim))


def load_codebook(path: str) -> PQCodebook | SQCodebook:
    z = np.load(path)
    kind = str(z["kind"])
    if kind == "pq":
        return PQCodebook(z["centroids"].astype(np.float32), int(z["dim"]))
    if kind == "sq":
        return SQCodebook(z["lo"].astype(np.float32),
                          z["scale"].astype(np.float32), int(z["dim"]))
    raise ValueError(f"unknown codebook kind {kind!r}")
