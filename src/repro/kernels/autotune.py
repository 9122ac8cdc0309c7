"""Tiling selection for the Chebyshev kernels: autotune-by-table.

Real autotuning (sweep + timing) is wasteful for a filter that is built
once and applied millions of times with a handful of distinct shapes.
Instead we keep a small table of preferred tiles keyed by coarse shape
buckets, and a deterministic VMEM-budget model decides whether the fused
kernel fits (DESIGN.md Sec. 6.3).

The decision this module makes:

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

__all__ = ["Tiling", "select_tiling", "union_vmem_bytes"]

# Bytes the fused working set may take of the compiler's scoped VMEM
# limit (16 MiB on v5e), leaving room for the compiler's own scratch.
# Interpret mode has no real budget but we keep the same decisions so CPU
# tests exercise the TPU code paths. ``tests/test_tpu_compile.py`` compiles
# a fused shape near this budget for v5e.
VMEM_BUDGET_BYTES = 12 * 1024 * 1024

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
    n_rows: int,
    k_max: int,
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
    (ping/pong) buffers in ``krylov_dtype`` and the (eta, N, f_tile) f32
    accumulators. ``krylov_dtype="bfloat16"`` halves the Krylov term,
    which is why the bf16 mode raises the fuse threshold in
    :func:`select_tiling`.
    """
    pipelined = (
        _vmem_array_bytes((n_rows, k_max, block, block), dtype)
        + _vmem_array_bytes((n, f_tile), dtype)
        + _vmem_array_bytes((eta, n, f_tile), dtype)
    )
    scratch = (
        2 * _vmem_array_bytes((n, f_tile), krylov_dtype)
        + _vmem_array_bytes((eta, n, f_tile), jnp.float32)
    )
    return 2 * pipelined + scratch


def select_tiling(
    n: int,
    f: int,
    eta: int,
    n_rows: int,
    k_max: int,
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
    n_rows, k_max, block : int
        Block-ELL operand shape.
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
        bytes_ = union_vmem_bytes(n, cand, eta, n_rows, k_max, block, dtype,
                                  krylov_dtype=krylov_dtype)
        if bytes_ <= vmem_budget:
            return Tiling(f_tile=cand, fuse=True, vmem_bytes=bytes_)
    return Tiling(
        f_tile=tiles[0],
        fuse=False,
        vmem_bytes=union_vmem_bytes(
            n, tiles[0], eta, n_rows, k_max, block, dtype,
            krylov_dtype=krylov_dtype,
        ),
    )
