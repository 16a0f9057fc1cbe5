"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracles."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import compile_filter, paper_schema, random_attributes, stack_programs
from repro.core import filters as F
from repro.kernels.filtered_topk import ops as ft_ops
from repro.kernels.filtered_topk import ref as ft_ref
from repro.kernels.gather_distance import ops as gd_ops
from repro.kernels.gather_distance import ref as gd_ref

SCHEMA = paper_schema()


def _db(n, d, seed=0):
    rng = np.random.default_rng(seed)
    vecs = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    norms = jnp.sum(vecs * vecs, axis=-1)
    attrs = random_attributes(SCHEMA, n, seed=seed + 1)
    return vecs, norms, jnp.asarray(attrs.ints), jnp.asarray(attrs.floats), rng


def _progs(b, rng):
    pool = [F.Equality("b0", True), F.Equality("i0", 3),
            F.Inclusion("i0", [1, 5, 9]), F.Range("f0", 10.0, 60.0),
            F.And(F.Equality("b0", False), F.Range("f0", None, 50.0)),
            F.Not(F.Range("f0", 30.0, 80.0)), F.TrueFilter()]
    flts = [pool[i % len(pool)] for i in range(b)]
    return {k: jnp.asarray(v) for k, v in
            stack_programs([compile_filter(f, SCHEMA) for f in flts]).items()}


# ---------------------------------------------------------------------------
# filtered_topk
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,d,b,k,bq,bn", [
    (700, 16, 12, 5, 4, 128),     # non-multiple row count (padding path)
    (1024, 32, 8, 10, 8, 256),
    (512, 64, 16, 10, 16, 512),   # one n-tile
    (2048, 8, 4, 32, 4, 256),     # large k
])
def test_filtered_topk_sweep(n, d, b, k, bq, bn):
    vecs, norms, ints, floats, rng = _db(n, d, seed=n + d)
    qs = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    progs = _progs(b, rng)
    ids, dd = ft_ops.filtered_topk(vecs, norms, ints, floats, qs, progs,
                                   k=k, block_q=bq, block_n=bn)
    rd, ri = ft_ref.filtered_topk_ref(qs, vecs, norms, ints, floats, progs,
                                      jnp.zeros((b,)), k=k, exclude=False)
    dd_c = np.where(np.isinf(np.asarray(dd)), ft_ref.BIG, np.asarray(dd))
    np.testing.assert_allclose(dd_c, np.asarray(rd), rtol=1e-5, atol=1e-5)
    # id agreement where distances are unique
    same = np.asarray(ids) == np.asarray(ri)
    assert same.mean() > 0.99


def test_filtered_topk_exclusion_mode():
    vecs, norms, ints, floats, rng = _db(1000, 24, seed=3)
    b = 8
    qs = jnp.asarray(rng.normal(size=(b, 24)).astype(np.float32))
    progs = _progs(b, rng)
    dvec = jnp.asarray(rng.uniform(0.1, 1.0, size=(b,)).astype(np.float32))
    ids, dd = ft_ops.filtered_topk(vecs, norms, ints, floats, qs, progs,
                                   k=10, dvec=dvec, exclude=True,
                                   block_q=8, block_n=256)
    rd, ri = ft_ref.filtered_topk_ref(qs, vecs, norms, ints, floats, progs,
                                      dvec, k=10, exclude=True)
    np.testing.assert_allclose(np.asarray(dd), np.asarray(rd), rtol=1e-5)
    assert (np.asarray(ids) == np.asarray(ri)).mean() > 0.99


def test_filtered_topk_schema_without_float_columns():
    """The kernels' 2-D program tables stay non-empty for a schema with no
    float column (one always-passing column stands in)."""
    schema = F.Schema((F.ColumnSpec("i0", "int", 10),))
    rng = np.random.default_rng(4)
    n, d, b = 600, 16, 4
    vecs = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    norms = jnp.sum(vecs * vecs, axis=-1)
    attrs = random_attributes(schema, n, seed=5)
    assert attrs.floats.shape == (n, 0)
    ints, floats = jnp.asarray(attrs.ints), jnp.asarray(attrs.floats)
    flts = [F.Equality("i0", 3), F.Inclusion("i0", [1, 2]), F.TrueFilter(),
            F.Not(F.Equality("i0", 7))]
    progs = {k: jnp.asarray(v) for k, v in
             stack_programs([compile_filter(f, schema) for f in flts]).items()}
    qs = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    ids, dd = ft_ops.filtered_topk(vecs, norms, ints, floats, qs, progs, k=8)
    rd, ri = ft_ref.filtered_topk_ref(qs, vecs, norms, ints, floats, progs,
                                      jnp.zeros((b,)), k=8, exclude=False)
    np.testing.assert_allclose(np.asarray(dd), np.asarray(rd), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ri))


def test_filtered_topk_matches_prefbf():
    """Kernel vs the production jnp PreFBF path (cross-validation)."""
    from repro.core import prefbf
    vecs, norms, ints, floats, rng = _db(1200, 16, seed=9)
    b = 6
    qs = jnp.asarray(rng.normal(size=(b, 16)).astype(np.float32))
    progs = _progs(b, rng)
    pv, pn, pi, pf = prefbf.pad_db(np.asarray(vecs), np.asarray(norms),
                                   np.asarray(ints), np.asarray(floats), 256)
    jid, jd = prefbf.prefbf_topk(jnp.asarray(pv), jnp.asarray(pn),
                                 jnp.asarray(pi), jnp.asarray(pf), qs, progs,
                                 k=10, chunk=256)
    kid, kd = ft_ops.filtered_topk(vecs, norms, ints, floats, qs, progs,
                                   k=10, block_q=8, block_n=256)
    np.testing.assert_allclose(np.asarray(jd), np.asarray(kd), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# gather_distance
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,d,b,m", [(300, 16, 4, 8), (600, 32, 6, 16),
                                     (128, 8, 2, 32)])
def test_gather_distance_sweep(n, d, b, m):
    vecs, norms, ints, floats, rng = _db(n, d, seed=n + m)
    qs = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    progs = _progs(b, rng)
    nbrs = rng.integers(-1, n, size=(b, m)).astype(np.int32)  # includes -1 pads
    dvec = jnp.asarray(rng.uniform(0.0, 1.0, size=(b,)).astype(np.float32))
    kd, ktd = gd_ops.gather_distance(vecs, norms, ints, floats, qs,
                                     jnp.asarray(nbrs), progs, dvec)
    rd, rtd = gd_ref.gather_distance_ref(jnp.asarray(nbrs), qs, vecs, norms,
                                         ints, floats, progs, dvec)
    rd_c = np.where(np.asarray(rd) >= gd_ref.BIG, np.inf, np.asarray(rd))
    np.testing.assert_allclose(np.asarray(kd), rd_c, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(ktd), np.asarray(rtd).astype(bool))


# ---------------------------------------------------------------------------
# pq_adc block-gather (graph-route scorer variant)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,d,b,m0,m,nbits", [
    (500, 16, 4, 8, 8, 6),
    (900, 24, 6, 16, 8, 8),   # includes -1 pads below
    (256, 8, 2, 32, 8, 5),    # 5-bit codes; dsub = 1 keeps ADC ~ true d2
])
def test_pq_adc_gather_sweep(n, d, b, m0, m, nbits):
    from repro.kernels.pq_adc import ops as pq_ops
    from repro.kernels.pq_adc import ref as pq_ref
    from repro.quant import encode, train_pq
    from repro.quant.adc import build_luts
    rng = np.random.default_rng(n + m0)
    vecs = rng.normal(size=(n, d)).astype(np.float32)
    cb = train_pq(vecs, m=m, nbits=nbits, iters=4, seed=0)
    codes = jnp.asarray(encode(cb, vecs))
    qs = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    luts = build_luts(jnp.asarray(cb.centroids), qs)
    nbrs = rng.integers(-1, n, size=(b, m0)).astype(np.int32)
    nbrs[:, 0] = -1          # force the pad path in every parametrization
    nbrs = jnp.asarray(nbrs)
    out = pq_ops.pq_adc_gather(codes, luts, nbrs)
    assert np.isinf(np.asarray(out)[:, 0]).all()   # -1 -> +inf contract
    ref = np.asarray(pq_ref.pq_adc_gather_ref(codes, luts, nbrs))
    ref = np.where(ref >= pq_ref.BIG, np.inf, ref)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-5)
    # the ADC sums really approximate the squared distances
    real = nbrs[0][np.asarray(nbrs[0]) >= 0]
    true2 = np.sum((np.asarray(qs)[0] - vecs[np.asarray(real)]) ** 2, axis=-1)
    approx = np.asarray(out)[0][np.asarray(nbrs[0]) >= 0]
    assert np.corrcoef(true2, approx)[0, 1] > 0.9


def test_pq_adc_gather_edge_rows():
    """Row-batched gather at awkward shapes: b not a block_q multiple, M0
    odd, one row entirely -1 pads -- oracle parity plus the all-inf
    contract for the padded row, for f32 and bf16 LUTs."""
    from repro.kernels.pq_adc import ops as pq_ops
    from repro.kernels.pq_adc import ref as pq_ref
    from repro.quant import encode, train_pq
    from repro.quant.adc import build_luts
    rng = np.random.default_rng(31)
    n, d, b, m0 = 300, 16, 3, 5
    vecs = rng.normal(size=(n, d)).astype(np.float32)
    cb = train_pq(vecs, m=8, nbits=8, iters=4, seed=0)
    codes = jnp.asarray(encode(cb, vecs))
    assert codes.dtype == jnp.uint8    # streamed uncast end-to-end
    qs = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    luts = build_luts(jnp.asarray(cb.centroids), qs)
    nbrs = rng.integers(0, n, size=(b, m0)).astype(np.int32)
    nbrs[1] = -1                       # a fully padded lane
    nbrs[0, 2] = -1
    nbrs = jnp.asarray(nbrs)
    ref = np.asarray(pq_ref.pq_adc_gather_ref(codes, luts, nbrs))
    ref = np.where(ref >= pq_ref.BIG, np.inf, ref)
    out = np.asarray(pq_ops.pq_adc_gather(codes, luts, nbrs))
    assert np.isinf(out[1]).all()
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
    # bf16 LUT storage: same gather, entries rounded -- stays within the
    # table's rounding error (~3 significant digits) of the f32 result
    out_bf = np.asarray(pq_ops.pq_adc_gather(
        codes, luts.astype(jnp.bfloat16), nbrs))
    assert np.isinf(out_bf[1]).all()
    fin = np.isfinite(ref)
    np.testing.assert_allclose(out_bf[fin], ref[fin], rtol=2e-2)


def test_pq_adc_gather_all_padded():
    """Every lane padded: the scalar-prefetch index_map must clamp the -1
    ids (no OOB row DMA) and the output is all +inf."""
    from repro.kernels.pq_adc import ops as pq_ops
    from repro.quant import encode, train_pq
    from repro.quant.adc import build_luts
    rng = np.random.default_rng(32)
    n, d, b, m0 = 128, 8, 4, 6
    vecs = rng.normal(size=(n, d)).astype(np.float32)
    cb = train_pq(vecs, m=4, nbits=6, iters=3, seed=1)
    codes = jnp.asarray(encode(cb, vecs))
    qs = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    luts = build_luts(jnp.asarray(cb.centroids), qs)
    nbrs = jnp.full((b, m0), -1, jnp.int32)
    out = np.asarray(pq_ops.pq_adc_gather(codes, luts, nbrs))
    assert out.shape == (b, m0)
    assert np.isinf(out).all()

