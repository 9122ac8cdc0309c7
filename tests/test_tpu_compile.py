"""Compile the Pallas kernels of the main path for a described TPU v5e chip.

No chip is attached: ``get_topology_desc`` describes one, and the TPU
compiler raises here what it would raise on the chip (SMEM or VMEM
exhaustion, block shapes the lowering refuses). Shapes are those of the
16,384-sensor smoke field (``chip_smoke.py``): F=128 signals, an eta=5
bank at order M=20, the Block-ELL tiling at block 8 and block 128.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every pytest worker imports this
file. Keep these cases in this one file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import autotune, ops
from repro.kernels.cheb_bsr import cheb_step_pallas, cheb_union_pallas

N = 16_384
F = 128
ETA = 5
ORDER = 20
LMAX = 30.0
# k_max of the smoke field's Block-ELL tiling (seed 0) at each block size.
K_MAX = {8: 13, 128: 10}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _bell_specs(sharding, n, block, k_max):
    n_rows = n // block
    return (_spec(sharding, (n_rows, k_max, block, block)),
            _spec(sharding, (n_rows, k_max), jnp.int32))


def _compile(fn, *specs) -> str:
    text = jax.jit(fn).lower(*specs).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _coeffs(eta, order):
    return tuple(tuple(1.0 / (1 + j + k) for k in range(order + 1))
                 for j in range(eta))


@pytest.mark.parametrize("block", [8, 128])
def test_step_kernel_compiles(one_chip, block):
    """The stepwise kernel at N=16384; block 8 once ran out of SMEM (its
    2-D prefetched column table padded to 128 words per block-row)."""
    blocks, cols = _bell_specs(one_chip, N, block, K_MAX[block])
    t = _spec(one_chip, (N, F))
    _compile(
        lambda b, c, t1, t2: cheb_step_pallas(b, c, t1, t2, alpha=LMAX / 2),
        blocks, cols, t, t)


def test_bf16_tiling_answer_compiles(one_chip):
    """What ``select_tiling`` answers for the smoke apply in bf16 Krylov
    mode compiles. It once fused at f_tile=8, a block the lowering
    refuses; the answer now is the stepwise chain at a 128-lane tile."""
    block, k_max = 8, K_MAX[8]
    tiling = autotune.select_tiling(
        N, F, ETA, N // block, k_max, block, krylov_dtype=jnp.bfloat16)
    assert tiling.f_tile % 128 == 0 or tiling.f_tile == F
    assert not tiling.fuse
    blocks, cols = _bell_specs(one_chip, N, block, k_max)
    _compile(
        lambda b, c, x, co: ops.cheb_apply_bsr(
            b, c, x, co, LMAX, f_tile=tiling.f_tile,
            krylov_dtype="bfloat16"),
        blocks, cols, _spec(one_chip, (N, F)),
        _spec(one_chip, (ETA, ORDER + 1)))


def test_fused_kernel_compiles_where_tiling_fuses(one_chip):
    """A shape ``select_tiling`` fuses in f32, close under its VMEM budget:
    the compiler must accept what the budget model admits."""
    n, block, k_max = 1024, 128, 2
    tiling = autotune.select_tiling(n, F, ETA, n // block, k_max, block)
    assert tiling.fuse
    assert tiling.vmem_bytes > autotune.VMEM_BUDGET_BYTES // 2
    blocks, cols = _bell_specs(one_chip, n, block, k_max)
    _compile(
        lambda b, c, x: cheb_union_pallas(
            b, c, x, coeffs=_coeffs(ETA, ORDER), lmax=LMAX,
            f_tile=tiling.f_tile),
        blocks, cols, _spec(one_chip, (n, F)))
