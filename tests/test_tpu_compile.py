"""Ahead-of-time compiles of the BMF Pallas kernels for a described TPU v5e.

Interpret-mode parity cannot see what Mosaic refuses (unaligned slices,
dot forms it cannot lower, VMEM limits), so the kernels are compiled here
for a v5e chip that is described, not attached, at the stripe shapes the
ops wrappers produce for the full-size MovieLens blocks: (8, 6656) is an
item-side stripe of phase a on an 8x8 grid, (24, 2304) a user-side one,
(8, 13312) the item side of phase a on the 4x4 grid ``chip_smoke.py``
runs (the widest index plane), plus (256, 256).  K pads to 128 lanes.
Both gather kernels get the (N,) per-row extent plane beside the index
plane: their shared row pump runs copy loops whose trip counts it reads
from SMEM, which interpret mode lowers without Mosaic's checks.
The sweep kernel also compiles at K=10 on the stripes of the benchmark's
MovieLens block, where its epilogue reads only the live corner of each
lane-padded tile.
The row sampler kernel compiles at the block shapes
of the benchmark's Netflix K=100 chain (15,006 user rows, 8,885 item
rows) and at the MovieLens user side of a 4x4 grid at K=10.

The topology is described inside a fixture (never at import), so every
pytest-xdist worker collects the same tests and only the worker that runs
this file loads the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bmf_precision.kernel import (
    LANES, precision_accum_fused_padded)
from repro.kernels.bmf_sample.kernel import sample_rows_kernel
from repro.kernels.bmf_sweep.kernel import fused_sweep_padded

STRIPES = [(8, 6656), (24, 2304), (8, 13312), (256, 256)]
D = 3410          # item rows of a full-size 8x8 block (the gathered factor)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache off meanwhile
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("N,M", STRIPES)
def test_precision_kernel_compiles_for_v5e(one_chip, N, M):
    S = lambda shape, dt: _sds(one_chip, shape, dt)
    f = jax.jit(lambda ix, ex, vl, mk, ot: precision_accum_fused_padded(
        ix, ex, vl, mk, ot, 2.0))
    compiled = f.lower(S((N, M), jnp.int32), S((N,), jnp.int32),
                       S((N, M), jnp.float32), S((N, M), jnp.float32),
                       S((D, LANES), jnp.float32)).compile()
    _assert_kernel(compiled)


# the K=10 cases are the stripes of ml20m-k10.chain's block (1,1) of 4x4:
# the U side gathers its 6,820 item rows, the V side its 34,623 user rows;
# their epilogue reads only the (16, 16) corner of each 128-lane tile
SWEEP_CASES = [pytest.param(N, M, D, None, id=f"{N}-{M}") for N, M in STRIPES
               ] + [pytest.param(128, 512, 6820, 10, id="128-512-k10"),
                    pytest.param(32, 1792, 34623, 10, id="32-1792-k10")]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("N,M,D,k", SWEEP_CASES)
def test_sweep_kernel_compiles_for_v5e(one_chip, N, M, D, k, dtype):
    S = lambda shape, dt: _sds(one_chip, shape, dt)
    f = jax.jit(lambda ix, ex, vl, mk, pe, pL, z, ot: fused_sweep_padded(
        ix, ex, vl, mk, pe, pL, z, ot, 2.0, dtype=dtype, k=k))
    compiled = f.lower(S((N, M), jnp.int32), S((N,), jnp.int32),
                       S((N, M), jnp.float32), S((N, M), jnp.float32),
                       S((N, LANES), jnp.float32),
                       S((N, LANES, LANES), jnp.float32),
                       S((N, LANES), jnp.float32),
                       S((D, LANES), jnp.float32)).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("N,K", [(15006, 100), (8885, 100), (34623, 10)])
def test_sample_kernel_compiles_for_v5e(one_chip, N, K):
    S = lambda shape: _sds(one_chip, shape, jnp.float32)
    f = jax.jit(sample_rows_kernel)
    _assert_kernel(f.lower(S((N, K, K)), S((N, K)), S((N, K))).compile())


def test_sample_kernel_vmap_compiles_for_v5e(one_chip):
    """The store's Thompson draws: 8 slots over MovieLens-20M's 27,278
    item rows at K=10, one Λ and a batch of noise draws."""
    S = lambda shape: _sds(one_chip, shape, jnp.float32)
    f = jax.jit(jax.vmap(sample_rows_kernel, in_axes=(None, None, 0)))
    N, K = 27278, 10
    _assert_kernel(f.lower(S((N, K, K)), S((N, K)),
                           S((8, N, K))).compile())
