"""The comm plane's kernels compiled for a described TPU v5e at real width.

The TPU compiler runs here without a chip: ``get_topology_desc`` describes
a v5e 2x2, and ``jit(...).lower(shapes).compile()`` runs Mosaic and XLA:TPU
as the chip would. It refuses misaligned blocks, VMEM overflow and HBM
overflow, which interpret mode cannot see. Nothing runs, so these tests
check only that each kernel compiles and is a real Mosaic kernel.

The shapes are mamba2-370m's flat comm-plane buffer: 368,338,432 f32
parameters, which make 2,877,696 rows × 128 lanes per worker.
- W = 2 compiles at the full row count.
- W = 16 compiles at an eighth of it. Sixteen full-width mirrors take
  23.6 GB per operand, more than one chip's 16 GB of HBM. An eighth
  keeps the byte count of the W = 2 case. It still tests the
  worker-chunk tiling (``MAX_WORKER_BLOCK``) and its VMEM budget.

The topology is described in a fixture, never at import. Only the
xdist worker that runs this file then loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.fastpath import kernels
from repro.fastpath.layout import BLOCK_ROWS, LANES, SUB_ROWS, FlatLayout
from repro.models import model

KERNELS = ("delta_sqnorm", "sqnorm", "absmax", "laq_encode",
           "masked_combine")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mamba_rows():
    """Plane rows per worker for mamba2-370m at its published widths."""
    cfg = get_config("mamba2-370m")
    shapes = jax.eval_shape(lambda k: model.init(k, cfg),
                            jax.random.PRNGKey(0))
    return FlatLayout.for_tree(shapes).rows


@pytest.fixture
def no_persistent_cache():
    """A described-chip compile cannot be read back from the persistent
    cache, so keep it out of there."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def test_mamba_rows_are_the_real_buffer(mamba_rows):
    assert mamba_rows == 2_877_696


def _case(name, W, R, sds):
    data = sds((W, R, LANES))
    if name == "delta_sqnorm":
        return (lambda a, b: kernels.delta_sqnorm_blocks(
            a, b, interpret=False)), (data, data)
    if name == "sqnorm":
        return (lambda a: kernels.sqnorm_blocks(a, interpret=False)), (data,)
    if name == "absmax":
        return (lambda g, q, e: kernels.absmax_blocks(
            g, q, e, interpret=False)), (data,) * 3
    if name == "laq_encode":
        return (lambda g, q, e, s: kernels.laq_encode_blocks(
            g, q, e, s, 4, interpret=False)), (data,) * 3 + (
            sds((W, R // SUB_ROWS)),)
    return (lambda a, b, m: kernels.masked_combine(
        a, b, m, "add", interpret=False)), (data, data,
                                            sds((W,), jnp.bool_))


@pytest.mark.parametrize("W", [2, 16])
@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, mamba_rows, no_persistent_cache,
                                 name, W):
    R = mamba_rows if W == 2 else (mamba_rows // 8) // BLOCK_ROWS * BLOCK_ROWS

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = _case(name, W, R, sds)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
