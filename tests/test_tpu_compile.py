"""Compile the Pallas kernels of the main path for a described TPU v5e chip.

No chip is attached: ``get_topology_desc`` describes one, and the TPU
compiler raises here what it would raise on the chip (SMEM or VMEM
exhaustion, block shapes the lowering refuses). Shapes are those of the
16,384-sensor smoke field (``chip_smoke.py``): F=128 signals, an eta=5
bank at order M=20, the Block-CSR tiling at every block size the ``bsr``
backend can choose; and the Graph500 graph at scale 14 in its degree
order, where one block-row holds far more tiles than the others.

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
from repro.kernels.cheb_bsr import cheb_step_pallas

N = 16_384
F = 128
ETA = 5
ORDER = 20
LMAX = 30.0
# Stored tiles of the smoke field's Block-CSR tiling (seed 0) at each block
# size ``autotune.choose_block_size`` weighs.
N_TILES = {8: 15_940, 16: 7_490, 32: 3_530, 64: 1_782, 128: 856, 256: 422}
# The same for the Graph500 graph at scale 14 (seed 0) at the blocks its
# chooser weighs most closely.
GRAPH500_TILES = {64: 17_456, 128: 6_346, 256: 2_018}


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


def _bsr_specs(sharding, block, n_tiles):
    return (_spec(sharding, (n_tiles, block, block)),
            _spec(sharding, (n_tiles,), jnp.int32),
            _spec(sharding, (n_tiles,), jnp.int32))


def _compile(fn, *specs) -> str:
    text = jax.jit(fn).lower(*specs).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("block,n_tiles", [
    *(pytest.param(b, t, id=str(b)) for b, t in sorted(N_TILES.items())),
    *(pytest.param(b, t, id=f"graph500-{b}") for b, t in sorted(GRAPH500_TILES.items()))])
def test_step_kernel_compiles(one_chip, block, n_tiles):
    """The stepwise kernel at N=16384 at every block the chooser can
    return; its two prefetched index tables take 8 bytes a stored tile of
    SMEM (a 2-D table once padded to 128 words per block-row and ran out
    at block 8)."""
    assert set(N_TILES) == set(autotune.BLOCK_CANDIDATES)
    t = _spec(one_chip, (N, F))
    _compile(
        lambda b, r, c, t1, t2: cheb_step_pallas(b, r, c, t1, t2, alpha=LMAX / 2),
        *_bsr_specs(one_chip, block, n_tiles), t, t)


@pytest.mark.parametrize("block", autotune.BLOCK_CANDIDATES)
def test_bf16_tiling_answer_compiles(one_chip, block):
    """The F tile ``choose_f_tile`` answers for the smoke apply compiles in
    bf16 Krylov mode at every block the chooser can return: a 128-lane
    tile, as in every cell (the lowering refuses a tile of 8 lanes)."""
    n_tiles = N_TILES[block]
    f_tile = autotune.choose_f_tile(F, block)
    assert f_tile == 128
    _compile(
        lambda b, r, c, x, co: ops.cheb_apply_bsr(
            b, r, c, x, co, LMAX, f_tile=f_tile, krylov_dtype="bfloat16"),
        *_bsr_specs(one_chip, block, n_tiles), _spec(one_chip, (N, F)),
        _spec(one_chip, (ETA, ORDER + 1)))


@pytest.mark.parametrize("block,n_tiles", [
    pytest.param(128, N_TILES[128], id="field-128"),
    pytest.param(256, GRAPH500_TILES[256], id="graph500-256")])
def test_two_panel_apply_compiles(one_chip, block, n_tiles):
    """``ops.cheb_apply_bsr`` at F=256, two panels' width, with the F tile
    it chooses itself, at the block each cell's graph gets."""
    _compile(
        lambda b, r, c, x, co: ops.cheb_apply_bsr(b, r, c, x, co, LMAX),
        *_bsr_specs(one_chip, block, n_tiles), _spec(one_chip, (N, 2 * F)),
        _spec(one_chip, (ETA, ORDER + 1)))
