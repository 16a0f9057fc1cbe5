"""The served-path Pallas kernels compile for a TPU v5e.

Nothing runs: each kernel is lowered and compiled against a *described*
v5e topology at the paper's width (d = 128), a 2^20-row DB, a bucket of
B = 256 queries, M0 = 32 neighbors per node and PQ m = 32 x K = 256.
Interpret mode cannot catch what these can: block tiling and VMEM
refusals.  The topology is described inside a module-scoped fixture (never
at import: only one process at a time may load the TPU library), and the
persistent compile cache is off around these compiles (entries compiled
for a described chip cannot be read back without one).
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.filtered_topk import ops as ft_ops
from repro.kernels.gather_distance import ops as gd_ops
from repro.kernels.pq_adc import ops as pq_ops

N, D, B, M0, W = 1 << 20, 128, 256, 32, 8
PQ_M, PQ_K = 32, 256
MI, MF = 2, 1           # paper schema: b0 + i0 int columns, f0 float column


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """ShapeDtypeStruct factory on one described chip, cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    one_chip = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _programs(s):
    return {"valid": s((B, W), jnp.float32), "imask": s((B, W, MI), jnp.uint32),
            "flo": s((B, W, MF), jnp.float32), "fhi": s((B, W, MF), jnp.float32)}


def _db(s):
    return (s((N,), jnp.float32), s((N, MI), jnp.int32),
            s((N, MF), jnp.float32))


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_filtered_topk_compiles_for_v5e(spec):
    norms, ints, floats = _db(spec)
    _assert_kernel(
        lambda v, n, i, f, q, p, val: ft_ops.filtered_topk(
            v, n, i, f, q, p, k=10, interpret=False, valid=val),
        spec((N, D), jnp.float32), norms, ints, floats,
        spec((B, D), jnp.float32), _programs(spec), spec((B,), jnp.bool_))


def test_pq_adc_topr_compiles_for_v5e(spec):
    norms, ints, floats = _db(spec)
    _assert_kernel(
        lambda c, n, i, f, lut, p, val: pq_ops.pq_adc_topr(
            c, n, i, f, lut, p, r=40, interpret=False, valid=val),
        spec((N, PQ_M), jnp.uint8), norms, ints, floats,
        spec((B, PQ_M, PQ_K), jnp.float32), _programs(spec),
        spec((B,), jnp.bool_))


def test_gather_distance_compiles_for_v5e(spec):
    # the block's per-row scalars come gathered, (B, M0) and (B, M0, m)
    _assert_kernel(
        lambda v, n, i, f, q, ids, p, dv: gd_ops.gather_distance(
            v, n, i, f, q, ids, p, dv, interpret=False),
        spec((N, D), jnp.float32), spec((B, M0), jnp.float32),
        spec((B, M0, MI), jnp.int32), spec((B, M0, MF), jnp.float32),
        spec((B, D), jnp.float32), spec((B, M0), jnp.int32),
        _programs(spec), spec((B,), jnp.float32))


@pytest.mark.parametrize("lut_dtype", [jnp.bfloat16, jnp.float32])
def test_pq_adc_gather_compiles_for_v5e(spec, lut_dtype):
    _assert_kernel(
        lambda c, lut, ids: pq_ops.pq_adc_gather(c, lut, ids,
                                                 interpret=False),
        spec((N, PQ_M), jnp.uint8), spec((B, PQ_M, PQ_K), lut_dtype),
        spec((B, M0), jnp.int32))
