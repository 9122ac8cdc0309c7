"""Tiling selection for the Chebyshev kernels: autotune-by-table.

Real autotuning (sweep + timing) is wasteful for a filter that is built
once and applied millions of times with a handful of distinct shapes.
Instead we keep a small table of preferred tiles keyed by coarse shape
buckets, and a deterministic VMEM-budget model decides whether the fused
kernel fits (DESIGN.md Sec. 6.3).

The decisions this module makes:

* ``block_size`` — the Block-CSR tile edge (:func:`choose_block_size`),
  from the Laplacian's own tile occupancy and a cost model, fitted on the
  chip, of the stepwise chain and of what the tiles add to set-up,
* ``f_tile``  — the F-dimension tile both kernels pipeline over,
* ``fuse``    — whether the fused union-combine kernel
  (:func:`repro.kernels.cheb_bsr.cheb_union_pallas`) fits: it keeps the
  whole (N, f_tile) Krylov state plus the (eta, N, f_tile) accumulators in
  VMEM, which is only legal while the working set stays under the budget.
  When it does not fit, callers chain the stepwise kernel instead.
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
    "Tiling",
    "choose_block_size",
    "select_tiling",
    "union_vmem_bytes",
]

# Bytes the fused working set may take of the compiler's scoped VMEM
# limit (16 MiB on v5e), leaving room for the compiler's own scratch.
# Interpret mode has no real budget but we keep the same decisions so CPU
# tests exercise the TPU code paths. ``tests/test_tpu_compile.py`` compiles
# a fused shape near this budget for v5e.
VMEM_BUDGET_BYTES = 12 * 1024 * 1024

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
class Tiling:
    """Resolved kernel launch configuration.

    Attributes:
      f_tile: F-dimension tile size (divides F).
      fuse: True when the fused union-combine kernel fits in VMEM.
      vmem_bytes: working-set estimate of the fused kernel at this tiling.
    """

    f_tile: int
    fuse: bool
    vmem_bytes: int


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


def union_vmem_bytes(
    n: int,
    f_tile: int,
    eta: int,
    n_tiles: int,
    block: int,
    dtype=jnp.float32,
    *,
    krylov_dtype=jnp.float32,
) -> int:
    """VMEM working set of the fused union kernel (bytes).

    Counts each array at its padded VMEM layout (``_vmem_array_bytes``).
    The pipelined operands — the resident Laplacian tiles, the input
    tile and the (eta, N, f_tile) output tile — are double-buffered by
    Pallas, so they count twice; the scratch counts once: two Krylov
    (ping/pong) buffers in ``krylov_dtype``, the (eta, N, f_tile) f32
    accumulators and one block-row of ``L @ T``.
    ``krylov_dtype="bfloat16"`` halves the Krylov term, which is why the
    bf16 mode raises the fuse threshold in :func:`select_tiling`.
    """
    pipelined = (
        _vmem_array_bytes((n_tiles, block, block), dtype)
        + _vmem_array_bytes((n, f_tile), dtype)
        + _vmem_array_bytes((eta, n, f_tile), dtype)
    )
    scratch = (
        2 * _vmem_array_bytes((n, f_tile), krylov_dtype)
        + _vmem_array_bytes((eta, n, f_tile), jnp.float32)
        + _vmem_array_bytes((block, f_tile), jnp.float32)
    )
    return 2 * pipelined + scratch


def select_tiling(
    n: int,
    f: int,
    eta: int,
    n_tiles: int,
    block: int,
    dtype=jnp.float32,
    vmem_budget: int = VMEM_BUDGET_BYTES,
    *,
    krylov_dtype=jnp.float32,
) -> Tiling:
    """Pick ``(f_tile, fuse)`` for a Chebyshev union apply.

    Parameters
    ----------
    n, f : int
        Padded signal shape (N, F).
    eta : int
        Number of multipliers in the union.
    n_tiles, block : int
        Block-CSR operand shape: stored tiles and their edge.
    dtype : jnp dtype
        Signal/Laplacian dtype.
    vmem_budget : int
        Bytes the fused working set may occupy.
    krylov_dtype : jnp dtype
        Krylov-buffer precision inside the fused kernel (bf16 halves
        that term of the working set, admitting larger fused shapes).

    Returns
    -------
    Tiling
        The widest legal ``f_tile`` (``_legal_f_tiles`` over the table's
        preferences) whose fused working set fits the budget, with
        ``fuse=True``; else the widest legal tile with ``fuse=False``.
    """
    dt_name = jnp.dtype(dtype).name
    tiles = _legal_f_tiles(f, _F_TILE_TABLE.get((block, dt_name), _DEFAULT_LADDER))
    # Widest legal tile that fuses; the widest one when none does.
    for cand in tiles:
        bytes_ = union_vmem_bytes(n, cand, eta, n_tiles, block, dtype,
                                  krylov_dtype=krylov_dtype)
        if bytes_ <= vmem_budget:
            return Tiling(f_tile=cand, fuse=True, vmem_bytes=bytes_)
    return Tiling(
        f_tile=tiles[0],
        fuse=False,
        vmem_bytes=union_vmem_bytes(
            n, tiles[0], eta, n_tiles, block, dtype,
            krylov_dtype=krylov_dtype,
        ),
    )


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
