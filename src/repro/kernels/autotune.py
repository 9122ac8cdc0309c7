"""Tiling choices for the stepwise Chebyshev kernel, fixed before it runs.

Real autotuning (sweep + timing) is wasteful for a filter that is built
once and applied millions of times with a handful of distinct shapes.
This module answers from the Laplacian and the signal's shape alone
(DESIGN.md Sec. 6.3):

* ``block_size`` — the Block-CSR tile edge (:func:`choose_block_size`),
  from the Laplacian's own tile occupancy and a cost model, fitted on the
  chip, of the stepwise chain and of what the tiles add to set-up,
* ``f_tile``  — the F-dimension tile ``cheb_step_pallas`` pipelines over
  (:func:`choose_f_tile`), from a small table of preferred tiles.
"""

from __future__ import annotations

import dataclasses
import math

import jax.numpy as jnp
import numpy as np

from repro.kernels.ref import stored_tiles, tile_mask

__all__ = [
    "BLOCK_CANDIDATES",
    "BlockChoice",
    "choose_block_size",
    "choose_f_tile",
]

# Block sizes ``choose_block_size`` weighs, finest first. Each is a whole
# number of finest slabs, so one occupancy mask at the finest serves all.
BLOCK_CANDIDATES = (8, 16, 32, 64, 128, 256)

# The stepwise chain's cost model (``_order_s``): one grid step of
# ``cheb_step_pallas``, one stored tile, costs STEP_S, plus the larger of
# its DMA (the tile and the gathered (block, f_tile) slab at
# HBM_BYTES_PER_S, the v5e's published bandwidth) and its
# HIGHEST-precision contraction (at MXU_F32_FLOPS_PER_S). STEP_S and MXU_F32_FLOPS_PER_S are fitted to a
# TPU v5e sweep of block_size 8..256 at the 16,384-sensor field, F=128:
# 0.223, 0.238, 0.260, 0.300, 0.366 and 0.711 us a step, each within 3%
# of the model (PERF.md, Findings).
STEP_S = 0.2165e-6
HBM_BYTES_PER_S = 819e9
MXU_F32_FLOPS_PER_S = 34e12

# Set-up seconds a byte of float32 tiles adds where a jit closes over the
# prepared filter, as the serving engine's ``jit(panel_apply)`` and a
# jitted ``GraphFilter.apply`` do: the program then embeds the tiles as a
# constant, and its persistent-cache load grows with their bytes. Slope
# of a least-squares line through the warm loads of such a program on a
# TPU v5e, 1.31, 2.25, 4.01 and 7.05 s at blocks 8, 64, 128 and 256 of
# the 16,384-sensor field (6.3, 37.7, 83.9 and 151 MB; PERF.md, Findings).
# A cold compile grows by about 7.4e-8 s a byte.
SETUP_S_PER_TILE_BYTE = 4.0e-8
# Applies of the filter that the tiles' set-up is weighed against. Both
# this and the slope above rest on that one field; at it the pick is 128
# from about 270 to 4,800 applies, 64 below and 256 above.
AMORTIZE_APPLIES = 1_000

# Preferred f_tile per (block_size bucket, dtype bucket), widest first:
# MXU-aligned 128 everywhere F allows it. No entry has been timed on a
# chip yet. Entries that are neither a multiple of 128 nor F itself are
# never used: Pallas TPU refuses a block whose last dim is neither (see
# ``_legal_f_tiles``). Unknown keys fall through to the default ladder.
_F_TILE_TABLE: dict[tuple[int, str], tuple[int, ...]] = {
    (8, "float32"): (128, 64, 32, 16, 8),
    (8, "bfloat16"): (128, 64, 32, 16),
    (16, "float32"): (128, 64, 32, 16),
    (16, "bfloat16"): (128, 64, 32, 16),
    (128, "float32"): (256, 128),
    (128, "bfloat16"): (256, 128),
}
_DEFAULT_LADDER = (256, 128, 64, 32, 16, 8)


@dataclasses.dataclass(frozen=True)
class BlockChoice:
    """The Block-CSR block size :func:`choose_block_size` picked.

    Attributes:
      block_size: the tile edge.
      order_s: predicted seconds of one order of the stepwise chain.
      base_order_s: the same at the finest candidate.
    """

    block_size: int
    order_s: float
    base_order_s: float


def _vmem_array_bytes(shape, dtype) -> int:
    """Bytes of an array in VMEM: the last dim pads to 128 lanes and the
    second-to-last to the dtype's sublane tile (8 rows of 32-bit words,
    16 of bf16), as Mosaic lays them out."""
    itemsize = jnp.dtype(dtype).itemsize
    *lead, rows, lanes = shape
    sublanes = 8 * (4 // itemsize)
    rows = -(-rows // sublanes) * sublanes
    lanes = -(-lanes // 128) * 128
    return math.prod(lead) * rows * lanes * itemsize


def _legal_f_tiles(f: int, preferred=_DEFAULT_LADDER) -> list[int]:
    """F tiles the TPU lowering accepts, widest first: divisors of F that
    are multiples of 128, or F itself (a block spanning the whole dim)."""
    tiles = [c for c in preferred if f % c == 0 and (c % 128 == 0 or c == f)]
    return tiles or [f]


def choose_f_tile(f: int, block: int, dtype=jnp.float32) -> int:
    """The F tile of the stepwise kernel for a signal of width ``f``: the
    widest tile the table prefers for ``(block, dtype)`` (the default
    ladder for a key it lacks) that the TPU lowering accepts
    (``_legal_f_tiles``)."""
    preferred = _F_TILE_TABLE.get((block, jnp.dtype(dtype).name), _DEFAULT_LADDER)
    return _legal_f_tiles(f, preferred)[0]


def _order_s(block: int, n_tiles: int) -> float:
    """Predicted seconds of one order of the stepwise chain
    (``cheb_step_pallas``) on float32 operands: one grid step per stored
    tile, each ``STEP_S + max(dma, mxu)``. HBM tiles the last two dims
    like VMEM, so a tile below 128 lanes moves as a padded one; the MXU
    contracts over 128-deep passes. The F tile is 128 (``min(F, 128)``:
    F is not known at prepare, and 128 is the width of a full panel)."""
    f_tile = 128
    dma = (_vmem_array_bytes((block, block), jnp.float32)
           + _vmem_array_bytes((block, f_tile), jnp.float32)) / HBM_BYTES_PER_S
    rows = -(-block // 8) * 8
    depth = -(-block // 128) * 128
    mxu = 2 * rows * depth * f_tile / MXU_F32_FLOPS_PER_S
    return n_tiles * (STEP_S + max(dma, mxu))


def choose_block_size(mask: np.ndarray, orders: int) -> BlockChoice:
    """The candidate block size of least predicted cost: the set-up its
    tiles add (``SETUP_S_PER_TILE_BYTE``) plus ``AMORTIZE_APPLIES`` applies
    of ``orders`` steps of the stepwise chain.

    ``mask`` is the Laplacian's tile mask (``kernels.ref.tile_mask``) at
    the finest candidate (``BLOCK_CANDIDATES[0]``) on the vertex order
    every candidate is built on. A coarse tile holds a nonzero iff one of
    its fine tiles does, so each coarser candidate's occupancy is pooled
    from it, with no second pass over the Laplacian; a candidate costs the
    tiles it stores (``kernels.ref.stored_tiles``). Candidates larger than
    the padded vertex count are not weighed. Ties go to the smaller block.
    """
    base = BLOCK_CANDIDATES[0]
    n_pad = mask.shape[0] * base
    priced = []  # (cost_s, block, order_s)
    for b in BLOCK_CANDIDATES:
        if b > n_pad:
            break
        n_tiles = int(stored_tiles(tile_mask(mask, b // base)).sum())
        order_s = _order_s(b, n_tiles)
        setup_s = SETUP_S_PER_TILE_BYTE * 4 * n_tiles * b * b
        priced.append((setup_s + AMORTIZE_APPLIES * orders * order_s, b, order_s))
    _, block, order_s = min(priced, key=lambda c: c[0])  # the first on ties
    return BlockChoice(block, order_s, base_order_s=priced[0][2])
