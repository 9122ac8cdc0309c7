"""Pallas TPU kernel: Block-CSR Laplacian matvec fused with the Chebyshev
recurrence step (paper eq. 9) — the compute hot-spot of the whole method.

Every Chebyshev order is ``T_k = (2/a) L T_{k-1} - 2 T_{k-1} - T_{k-2}``.
A naive implementation issues an SpMV and two AXPYs, round-tripping
``T_{k-1}``/``T_k`` through HBM three times per order. This kernel fuses the
whole step: one pass over the Laplacian tiles, the affine combine applied in
VMEM before the single store of ``T_k``.

TPU adaptation (DESIGN.md Sec. 3): the GPU-idiomatic CSR gather-per-row is
replaced by Block-CSR — ordered vertices give few dense ``(block x block)``
tiles; each tile multiply is an MXU contraction against an ``F``-wide
signal batch. The data-dependent tile gather uses **scalar prefetch**: the
block-row and block-column of every stored tile live in SMEM and feed the
BlockSpec index_maps, so Pallas pipelines the HBM->VMEM tile streams
without kernel-visible gathers. Both tables are 1-D, ``(n_tiles,)``:
SMEM pads the last dim of a 2-D operand to 128 words.

Grid: ``(F_tiles, n_tiles)``, one step per stored tile, tiles sorted by
block-row. The output block ``(rows[t], fi)`` is revisited over a row's
consecutive steps and accumulates in VMEM (init on the row's first tile,
combine on its last); a row's tile count costs nothing on another row.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["cheb_step_pallas"]

# Tile contractions run at full f32 precision (see _cheb_step_kernel).
_F32 = jax.lax.Precision.HIGHEST


def _row_ends(rows_ref, t, n_tiles: int):
    """Whether stored tile ``t`` opens its block-row, and whether it
    closes it (tiles are sorted by block-row)."""
    row = rows_ref[t]
    first = (t == 0) | (rows_ref[jnp.maximum(t - 1, 0)] != row)
    last = (t == n_tiles - 1) | (rows_ref[jnp.minimum(t + 1, n_tiles - 1)] != row)
    return first, last


def _cheb_step_kernel(
    # scalar-prefetch operands
    rows_ref,  # (n_tiles,) int32, SMEM
    cols_ref,  # (n_tiles,) int32, SMEM
    # tensor operands
    blocks_ref,  # (1, B, B)         Laplacian tile t
    t1g_ref,  # (B, FT)            gathered T_{k-1}[cols[t]]
    t1s_ref,  # (B, FT)            aligned  T_{k-1}[rows[t]]
    t2s_ref,  # (B, FT)            aligned  T_{k-2}[rows[t]]
    out_ref,  # (B, FT)            T_k[rows[t]]
    acc_ref,  # (B, FT) f32 VMEM scratch — accumulator survives the row's tiles
    *,
    n_tiles: int,
    ca: float,
    cb: float,
    cc: float,
):
    first, last = _row_ends(rows_ref, pl.program_id(1), n_tiles)

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # MXU contraction for this Laplacian tile; accumulate L @ t1 in f32
    # VMEM scratch (bf16 inputs still accumulate at full precision).
    # HIGHEST: Mosaic's default f32 contraction rounds the operands to
    # bf16 (4e-3 relative error per apply on a v5e).
    acc_ref[...] += jnp.dot(
        blocks_ref[0].astype(jnp.float32),
        t1g_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
        precision=_F32,
    )

    @pl.when(last)
    def _combine():
        # Fused affine recurrence: T_k = ca * (L t1) + cb * t1 + cc * t2,
        # combined in f32 and cast once on the single store of T_k.
        out_ref[...] = (
            ca * acc_ref[...]
            + cb * t1s_ref[...].astype(jnp.float32)
            + cc * t2s_ref[...].astype(jnp.float32)
        ).astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("alpha", "first", "f_tile", "interpret"),
)
def cheb_step_pallas(
    blocks: jax.Array,
    rows: jax.Array,
    cols: jax.Array,
    t1: jax.Array,
    t2: jax.Array,
    *,
    alpha: float,
    first: bool = False,
    f_tile: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """One fused Chebyshev recurrence step on Block-CSR operands.

    Args:
      blocks: (n_tiles, B, B) Laplacian tiles, sorted by block-row.
      rows:   (n_tiles,) int32 block-row of each tile; every block-row of
        ``t1`` holds at least one tile (``kernels/ref.py``).
      cols:   (n_tiles,) int32 block-column of each tile.
      t1: (N, F) ``T_{k-1}`` with N a multiple of B.
      t2: (N, F) ``T_{k-2}`` (pass t1 when ``first=True``; ignored).
      alpha: lmax / 2 spectrum shift.
      first: compute ``T_1 = (L - a I) f / a`` instead of the k >= 2 step.
      f_tile: F-dimension tile (defaults to min(F, 128)).
      interpret: run in Pallas interpret mode (CPU validation path).

    Returns: (N, F) ``T_k``.
    """
    n_tiles, b, b2 = blocks.shape
    assert b == b2, blocks.shape
    n, f = t1.shape
    assert n % b == 0, (t1.shape, blocks.shape)
    ft = f_tile or min(f, 128)
    assert f % ft == 0, (f, ft)

    if first:
        ca, cb, cc = 1.0 / alpha, -1.0, 0.0
    else:
        ca, cb, cc = 2.0 / alpha, -2.0, -1.0

    kernel = functools.partial(
        _cheb_step_kernel, n_tiles=n_tiles, ca=ca, cb=cb, cc=cc
    )

    def row(fi, t, rows, cols):
        return rows[t], fi

    grid = (f // ft, n_tiles)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, b, b), lambda fi, t, rows, cols: (t, 0, 0)),
                pl.BlockSpec(  # gathered t1 rows via scalar-prefetched cols
                    (b, ft), lambda fi, t, rows, cols: (cols[t], fi)
                ),
                pl.BlockSpec((b, ft), row),
                pl.BlockSpec((b, ft), row),
            ],
            out_specs=pl.BlockSpec((b, ft), row),
            scratch_shapes=[pltpu.VMEM((b, ft), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n, f), t1.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="cheb_bsr_step",
    )(rows, cols, blocks, t1, t1, t2)
