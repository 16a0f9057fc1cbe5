"""Quantization subsystem: codebooks, ADC scans, Pallas kernel, e2e recall."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (BuildSpec, FavorIndex, QuantSpec, SearchOptions,
                        compile_filter, paper_filters, paper_schema,
                        random_attributes, stack_programs)
from repro.core import filters as F
from repro.core import refimpl
from repro.kernels.pq_adc import ops as pq_ops
from repro.kernels.pq_adc import ref as pq_ref
from repro.quant import (build_luts, decode, encode, load_codebook,
                         pq_prefbf_topk, save_codebook, train_pq, train_sq)

SCHEMA = paper_schema()


def _data(n, d, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(n, d)).astype(np.float32), rng


# ---------------------------------------------------------------------------
# codebooks
# ---------------------------------------------------------------------------
def test_pq_train_encode_decode_roundtrip():
    x, _ = _data(1500, 16, seed=1)
    cb = train_pq(x, m=8, nbits=6, iters=15, seed=0)
    assert cb.centroids.shape == (8, 64, 2)
    codes = encode(cb, x)
    assert codes.shape == (1500, 8) and codes.dtype == np.uint8
    recon = decode(cb, codes)
    assert recon.shape == x.shape
    mse = float(np.mean((recon - x) ** 2))
    assert mse < 0.5 * float(np.var(x)), "codebooks did not learn the data"


def test_pq_nondividing_dim():
    x, _ = _data(800, 10, seed=2)  # 10 dims over m=4 -> dsub=3, 2 pad dims
    cb = train_pq(x, m=4, nbits=5, iters=10, seed=0)
    assert cb.dsub == 3 and cb.padded_dim == 12 and cb.dim == 10
    recon = decode(cb, encode(cb, x))
    assert recon.shape == x.shape


def test_sq_roundtrip_error_bound():
    x, _ = _data(500, 12, seed=3)
    cb = train_sq(x)
    codes = encode(cb, x)
    assert codes.dtype == np.uint8 and codes.shape == x.shape
    recon = decode(cb, codes)
    # affine int8: per-dim error is at most half a quantization step
    assert np.all(np.abs(recon - x) <= 0.5 * cb.scale[None, :] + 1e-6)


def test_codebook_save_load(tmp_path):
    x, _ = _data(600, 8, seed=4)
    for cb in (train_pq(x, m=4, nbits=4, iters=5), train_sq(x)):
        p = str(tmp_path / "cb.npz")
        save_codebook(p, cb)
        cb2 = load_codebook(p)
        assert type(cb2) is type(cb) and cb2.dim == cb.dim
        np.testing.assert_array_equal(encode(cb, x), encode(cb2, x))


# ---------------------------------------------------------------------------
# ADC vs exact distances
# ---------------------------------------------------------------------------
def test_adc_distance_error_bound():
    x, rng = _data(2000, 16, seed=5)
    cb = train_pq(x, m=8, nbits=6, iters=15, seed=0)
    codes = jnp.asarray(encode(cb, x))
    qs = jnp.asarray(rng.normal(size=(8, 16)).astype(np.float32))
    luts = build_luts(jnp.asarray(cb.centroids), qs)
    idx = codes.astype(jnp.int32)[None, :, :, None]
    adc = jnp.sum(jnp.take_along_axis(luts[:, None], idx, axis=3)[..., 0], -1)
    exact = np.linalg.norm(np.asarray(qs)[:, None, :] - x[None], axis=-1)
    err = np.abs(np.sqrt(np.asarray(adc)) - exact)
    assert float(np.mean(err)) / float(np.mean(exact)) < 0.1, \
        "ADC distances drifted too far from exact"


# ---------------------------------------------------------------------------
# Pallas kernel vs ref oracle (interpret mode on CPU)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,b,m,nbits,r,bq,bn", [
    (700, 6, 8, 6, 20, 4, 128),    # non-multiple row count (padding path)
    (1024, 8, 4, 8, 10, 8, 256),
    (512, 4, 16, 4, 40, 4, 512),   # one n-tile, large R
    (768, 8, 32, 8, 40, 8, 256),   # the sift128-pq widths: PQ32, K=256, R=40
])
def test_pq_adc_kernel_matches_ref(n, b, m, nbits, r, bq, bn):
    rng = np.random.default_rng(n + m)
    k = 1 << nbits
    codes = jnp.asarray(rng.integers(0, k, size=(n, m)).astype(np.uint8))
    luts = jnp.asarray(rng.uniform(0, 4.0, size=(b, m, k)).astype(np.float32))
    norms = jnp.asarray(rng.uniform(1.0, 2.0, size=(n,)).astype(np.float32))
    attrs = random_attributes(SCHEMA, n, seed=n)
    ints, floats = jnp.asarray(attrs.ints), jnp.asarray(attrs.floats)
    pool = [F.Equality("b0", True), F.Inclusion("i0", [1, 5, 9]),
            F.Range("f0", 10.0, 60.0), F.TrueFilter()]
    progs = {kk: jnp.asarray(v) for kk, v in stack_programs(
        [compile_filter(pool[i % len(pool)], SCHEMA) for i in range(b)]).items()}

    ids, dd = pq_ops.pq_adc_topr(codes, norms, ints, floats, luts, progs,
                                 r=r, block_q=bq, block_n=bn)
    rd, ri = pq_ref.pq_adc_topr_ref(luts, codes, norms, ints, floats, progs,
                                    r=r)
    dd_c = np.where(np.isinf(np.asarray(dd)), pq_ref.BIG, np.asarray(dd))
    np.testing.assert_allclose(dd_c, np.asarray(rd), rtol=1e-5, atol=1e-5)
    same = np.asarray(ids) == np.asarray(ri)
    assert same.mean() > 0.99  # ids agree where ADC values are unique


def test_pq_adc_kernel_matches_jnp_scan():
    """Pallas route of pq_prefbf_topk vs the jnp lax.scan route."""
    x, rng = _data(1200, 16, seed=7)
    cb = train_pq(x, m=8, nbits=6, iters=10, seed=0)
    from repro.core import prefbf
    attrs = random_attributes(SCHEMA, 1200, seed=8)
    pv, pn, pi, pf = prefbf.pad_db(x, np.einsum("nd,nd->n", x, x),
                                   attrs.ints, attrs.floats, 256)
    codes = jnp.asarray(encode(cb, pv))
    qs = jnp.asarray(rng.normal(size=(6, 16)).astype(np.float32))
    progs = {kk: jnp.asarray(v) for kk, v in stack_programs(
        [compile_filter(F.Range("f0", 20.0, 80.0), SCHEMA)] * 6).items()}
    args = (codes, jnp.asarray(pn), jnp.asarray(pi), jnp.asarray(pf), qs,
            progs, jnp.asarray(cb.centroids), jnp.asarray(pv))
    ji, jd = pq_prefbf_topk(*args, k=10, rerank=2, chunk=256)
    ki, kd = pq_prefbf_topk(*args, k=10, rerank=2, chunk=256, use_pallas=True)
    np.testing.assert_allclose(np.asarray(jd), np.asarray(kd),
                               rtol=1e-5, atol=1e-5)
    assert (np.asarray(ji) == np.asarray(ki)).mean() > 0.99


# ---------------------------------------------------------------------------
# end-to-end recall through FavorIndex
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def quant_index(small_index, small_dataset):
    vecs, attrs, _ = small_dataset
    return FavorIndex(small_index.index, attrs, BuildSpec(quant=QuantSpec(
        kind="pq", m=8, nbits=6, train_iters=15, rerank=4)))


def test_use_pq_requires_quantized_index(small_index, small_dataset):
    vecs, _, schema = small_dataset
    qs = np.zeros((2, vecs.shape[1]), np.float32)
    with pytest.raises(ValueError, match="quantize"):
        small_index.query(qs, F.TrueFilter(), SearchOptions(k=5, use_pq=True))


def test_e2e_pq_recall_within_2pts(quant_index, small_dataset):
    vecs, attrs, schema = small_dataset
    rng = np.random.default_rng(21)
    qs = rng.normal(size=(16, vecs.shape[1])).astype(np.float32)
    for name, flt in paper_filters(schema).items():
        mask = F.eval_program(compile_filter(flt, schema), attrs.ints,
                              attrs.floats)
        truth = [refimpl.bruteforce_filtered(vecs, mask, q, 10)[0] for q in qs]
        r_f32 = quant_index.query(qs, flt, SearchOptions(k=10, force="brute"))
        r_pq = quant_index.query(qs, flt, SearchOptions(k=10, force="brute",
                                                        use_pq=True))
        rec_f32 = np.mean([refimpl.recall_at_k(i[i >= 0], t, 10)
                           for i, t in zip(r_f32.ids, truth)])
        rec_pq = np.mean([refimpl.recall_at_k(i[i >= 0], t, 10)
                          for i, t in zip(r_pq.ids, truth)])
        assert rec_pq >= rec_f32 - 0.02, \
            f"{name}: pq recall {rec_pq:.3f} < f32 {rec_f32:.3f} - 0.02"


def test_e2e_pq_routed_search(quant_index, small_dataset):
    """Default (selector-routed) search works with use_pq: graph queries are
    untouched, brute queries go through the compressed scan."""
    vecs, _, schema = small_dataset
    rng = np.random.default_rng(22)
    qs = rng.normal(size=(8, vecs.shape[1])).astype(np.float32)
    flt = paper_filters(schema)["range_50"]
    res = quant_index.query(qs, flt, SearchOptions(k=10, use_pq=True))
    assert np.all(np.sort(res.dists, axis=1) == res.dists)
    assert res.ids.shape == (8, 10)


def test_sq_fallback_e2e(small_index, small_dataset):
    vecs, attrs, schema = small_dataset
    fi = FavorIndex(small_index.index, attrs,
                    BuildSpec(quant=QuantSpec(kind="sq", rerank=4)))
    assert fi.bytes_per_vector(quantized=True) == vecs.shape[1]
    rng = np.random.default_rng(23)
    qs = rng.normal(size=(6, vecs.shape[1])).astype(np.float32)
    flt = paper_filters(schema)["equality_bool"]
    r_f32 = small_index.query(qs, flt, SearchOptions(k=10, force="brute"))
    r_sq = fi.query(qs, flt, SearchOptions(k=10, force="brute", use_pq=True))
    # int8 scalar quantization + 4x re-rank recovers the exact top-10 here
    assert (r_sq.ids == r_f32.ids).mean() > 0.95


def test_index_save_load_roundtrip_with_codebook(quant_index, small_dataset,
                                                 tmp_path):
    vecs, _, schema = small_dataset
    path = str(tmp_path / "idx")
    quant_index.save(path)
    fi2 = FavorIndex.load(path)
    assert fi2.quantize == "pq"
    np.testing.assert_array_equal(np.asarray(fi2._codes),
                                  np.asarray(quant_index._codes))
    rng = np.random.default_rng(24)
    qs = rng.normal(size=(4, vecs.shape[1])).astype(np.float32)
    flt = paper_filters(schema)["inclusion"]
    opts = SearchOptions(k=10, force="brute", use_pq=True)
    r1 = quant_index.query(qs, flt, opts)
    r2 = fi2.query(qs, flt, opts)
    np.testing.assert_array_equal(r1.ids, r2.ids)
