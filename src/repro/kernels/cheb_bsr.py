"""Pallas TPU kernel: Block-ELL Laplacian matvec fused with the Chebyshev
recurrence step (paper eq. 9) — the compute hot-spot of the whole method.

Every Chebyshev order is ``T_k = (2/a) L T_{k-1} - 2 T_{k-1} - T_{k-2}``.
A naive implementation issues an SpMV and two AXPYs, round-tripping
``T_{k-1}``/``T_k`` through HBM three times per order. This kernel fuses the
whole step: one pass over the Laplacian tiles, the affine combine applied in
VMEM before the single store of ``T_k``.

TPU adaptation (DESIGN.md Sec. 3): the GPU-idiomatic CSR gather-per-row is
replaced by Block-ELL — spatially-ordered vertices give few dense
``(block x block)`` tiles per block-row; each tile multiply is an MXU
contraction against an ``F``-wide signal batch. The data-dependent tile
gather uses **scalar prefetch**: block-column indices live in SMEM and feed
the BlockSpec index_map, so Pallas pipelines the HBM->VMEM tile streams
without kernel-visible gathers. The indices are prefetched flat,
``(n_rows * k_max,)``: SMEM pads the last dim of a 2-D operand to 128
words, so a 2-D ``(n_rows, k_max)`` table would cost 512 B per block-row
whatever ``k_max`` is, and 2048 block-rows would fill the whole 1 MiB.

Grid: ``(F_tiles, n_block_rows, k_max)`` with the sparse-column loop
innermost — the output block revisits k_max times and accumulates in VMEM
(init at j == 0, combine at j == k_max - 1).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["cheb_step_pallas", "cheb_union_pallas"]

# Tile contractions run at full f32 precision (see _cheb_step_kernel).
_F32 = jax.lax.Precision.HIGHEST


def _cheb_step_kernel(
    # scalar-prefetch operands
    cols_ref,  # (n_rows * k_max,) int32, SMEM
    # tensor operands
    blocks_ref,  # (1, 1, B, B)    Laplacian tile for (i, j)
    t1g_ref,  # (B, FT)            gathered T_{k-1}[cols[i, j]]
    t1s_ref,  # (B, FT)            aligned  T_{k-1}[i]
    t2s_ref,  # (B, FT)            aligned  T_{k-2}[i]
    out_ref,  # (B, FT)            T_k[i]
    acc_ref,  # (B, FT) f32 VMEM scratch — accumulator survives the j loop
    *,
    k_max: int,
    ca: float,
    cb: float,
    cc: float,
):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # MXU contraction for this Laplacian tile; accumulate L @ t1 in f32
    # VMEM scratch (bf16 inputs still accumulate at full precision).
    # HIGHEST: Mosaic's default f32 contraction rounds the operands to
    # bf16 (4e-3 relative error per apply on a v5e).
    acc_ref[...] += jnp.dot(
        blocks_ref[0, 0].astype(jnp.float32),
        t1g_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
        precision=_F32,
    )

    @pl.when(j == k_max - 1)
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
    cols: jax.Array,
    t1: jax.Array,
    t2: jax.Array,
    *,
    alpha: float,
    first: bool = False,
    f_tile: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """One fused Chebyshev recurrence step on Block-ELL operands.

    Args:
      blocks: (n_rows, k_max, B, B) Laplacian tiles.
      cols:   (n_rows, k_max) int32 block-column ids (padding: col 0 +
        zero tile).
      t1: (N, F) ``T_{k-1}`` with N = n_rows * B.
      t2: (N, F) ``T_{k-2}`` (pass t1 when ``first=True``; ignored).
      alpha: lmax / 2 spectrum shift.
      first: compute ``T_1 = (L - a I) f / a`` instead of the k >= 2 step.
      f_tile: F-dimension tile (defaults to min(F, 128)).
      interpret: run in Pallas interpret mode (CPU validation path).

    Returns: (N, F) ``T_k``.
    """
    n_rows, k_max, b, b2 = blocks.shape
    assert b == b2, blocks.shape
    n, f = t1.shape
    assert n == n_rows * b, (t1.shape, blocks.shape)
    ft = f_tile or min(f, 128)
    assert f % ft == 0, (f, ft)

    if first:
        ca, cb, cc = 1.0 / alpha, -1.0, 0.0
    else:
        ca, cb, cc = 2.0 / alpha, -2.0, -1.0

    kernel = functools.partial(
        _cheb_step_kernel, k_max=k_max, ca=ca, cb=cb, cc=cc
    )

    grid = (f // ft, n_rows, k_max)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (1, 1, b, b), lambda fi, i, j, cols: (i, j, 0, 0)
                ),
                pl.BlockSpec(  # gathered t1 rows via scalar-prefetched cols
                    (b, ft), lambda fi, i, j, cols: (cols[i * k_max + j], fi)
                ),
                pl.BlockSpec((b, ft), lambda fi, i, j, cols: (i, fi)),
                pl.BlockSpec((b, ft), lambda fi, i, j, cols: (i, fi)),
            ],
            out_specs=pl.BlockSpec((b, ft), lambda fi, i, j, cols: (i, fi)),
            scratch_shapes=[pltpu.VMEM((b, ft), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n, f), t1.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(cols.reshape(-1), blocks, t1, t1, t2)


# ---------------------------------------------------------------------------
# Fused union-combine kernel: the whole Chebyshev apply in ONE pallas_call.
# ---------------------------------------------------------------------------


def _cheb_union_kernel(
    # scalar-prefetch operand
    cols_ref,  # (n_rows * k_max,) int32, SMEM
    # tensor operands
    blocks_ref,  # (n_rows, k_max, B, B) — the whole Block-ELL Laplacian
    f_ref,  # (N, FT)                     input signal tile (= T_0)
    out_ref,  # (eta, N, FT)              combined outputs, one per multiplier
    ta_ref,  # (N, FT) VMEM scratch — T_k ping buffer (krylov_dtype)
    tb_ref,  # (N, FT) VMEM scratch — T_k pong buffer (krylov_dtype)
    acc_ref,  # (eta, N, FT) f32 VMEM scratch — eq. 11 accumulators
    *,
    coeffs: tuple[tuple[float, ...], ...],
    alpha: float,
    n_rows: int,
    k_max: int,
    block: int,
    ft: int,
):
    """Run eq. 9 + eq. 11 entirely in VMEM.

    The recurrence alternates between two (N, FT) scratch buffers; the j-th
    accumulator picks up ``c_{j,k} * T_k`` inside the same row loop that
    produces ``T_k``, so no order's ``T_k`` is ever stored to HBM. The
    in-place pong write is safe: row ``i`` of ``T_{k-2}`` is consumed
    (aligned read) in the same loop iteration that overwrites it, and the
    gathered operand is always the *other* buffer (``T_{k-1}``).

    Krylov precision: the ping/pong buffers carry ``krylov_dtype`` (the
    pallas_call picks the scratch dtype); every step still computes in f32
    and the accumulators pick up the *pre-rounding* f32 ``T_k`` — only the
    value the next recurrence step reads back is rounded. With f32 buffers
    every cast is a no-op, so the f32 path is bit-identical to the
    pre-``krylov_dtype`` kernel; bf16 buffers halve the Krylov VMEM
    footprint (see ``autotune.union_vmem_bytes``).
    """
    eta = len(coeffs)
    order = len(coeffs[0]) - 1
    f32 = jnp.float32

    def spmv_row(src_ref, i):
        """(L @ src)[i-th block row] via scalar-prefetched tile gather."""
        acc = jnp.zeros((block, ft), f32)
        for j in range(k_max):
            c = cols_ref[i * k_max + j]
            seg = src_ref[pl.ds(c * block, block), :]
            acc += jnp.dot(
                blocks_ref[i, j].astype(f32), seg.astype(f32),
                preferred_element_type=f32, precision=_F32,
            )
        return acc

    # ---- k = 0, 1:  T_1 = (L - aI) f / a, accumulators initialised -------
    def init_row(i, _):
        sl = pl.ds(i * block, block)
        t0 = f_ref[sl, :].astype(f32)
        t1 = spmv_row(f_ref, i) / alpha - t0
        ta_ref[sl, :] = t1.astype(ta_ref.dtype)
        for j in range(eta):
            acc_ref[j, sl, :] = coeffs[j][0] * 0.5 * t0 + coeffs[j][1] * t1
        return 0

    jax.lax.fori_loop(0, n_rows, init_row, 0, unroll=False)

    # ---- k >= 2: ping-pong the recurrence, combine in the same pass ------
    def make_step(k, src1_ref, src0_ref, dst_ref):
        # src0 may alias dst: T_k overwrites T_{k-2} row by row (see above).
        def step_row(i, _):
            sl = pl.ds(i * block, block)
            lx = spmv_row(src1_ref, i)
            t_new = (
                (2.0 / alpha) * lx
                - 2.0 * src1_ref[sl, :].astype(f32)
                - src0_ref[sl, :].astype(f32)
            )
            dst_ref[sl, :] = t_new.astype(dst_ref.dtype)
            for j in range(eta):
                acc_ref[j, sl, :] += coeffs[j][k] * t_new
            return 0

        jax.lax.fori_loop(0, n_rows, step_row, 0, unroll=False)

    for k in range(2, order + 1):
        if k == 2:
            # T_0 still lives in the (read-only) input tile.
            make_step(k, ta_ref, f_ref, tb_ref)
        elif k % 2 == 1:
            make_step(k, tb_ref, ta_ref, ta_ref)
        else:
            make_step(k, ta_ref, tb_ref, tb_ref)

    out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("coeffs", "lmax", "f_tile", "interpret", "krylov_dtype"),
)
def cheb_union_pallas(
    blocks: jax.Array,
    cols: jax.Array,
    f: jax.Array,
    *,
    coeffs: tuple[tuple[float, ...], ...],
    lmax: float,
    f_tile: int | None = None,
    interpret: bool = False,
    krylov_dtype: str = "float32",
) -> jax.Array:
    """Full union apply ``Phi~ f`` in a single fused ``pallas_call``.

    Fuses the recurrence (eq. 9) *and* the union combine (eq. 11): the
    per-signal-tile state — two Krylov buffers plus the ``eta``
    accumulators — lives in VMEM for the whole apply, so intermediate
    ``T_k`` tensors are never materialized to HBM (the stepwise
    ``cheb_apply_bsr`` chain stores each ``T_k`` once per order).

    Requires the working set to fit in VMEM; use
    :func:`repro.kernels.autotune.select_tiling` to decide between this
    kernel and the stepwise fallback, and to pick ``f_tile``.

    Parameters
    ----------
    blocks : jax.Array
        (n_rows, k_max, B, B) Block-ELL Laplacian tiles.
    cols : jax.Array
        (n_rows, k_max) int32 block-column ids (padding: col 0 + zero tile).
    f : jax.Array
        (N, F) signal batch, ``N = n_rows * B``.
    coeffs : tuple of tuples
        Static (eta, M+1) Chebyshev coefficients (hashable: one compile per
        filter, matching the build-once / apply-many filter lifecycle).
    lmax : float
        Static spectrum upper bound.
    f_tile : int, optional
        F-dimension tile; defaults to ``min(F, 128)``.
    interpret : bool
        Run in Pallas interpret mode (CPU validation path).
    krylov_dtype : str
        Static dtype of the two VMEM Krylov (ping/pong) buffers —
        ``"float32"`` (default, bit-identical to the historic kernel) or
        ``"bfloat16"`` (halves the Krylov working set; the recurrence
        still computes and accumulates in f32, only the stored ``T_k``
        round-trips through bf16).

    Returns
    -------
    jax.Array
        (eta, N, F) stacked filter outputs.
    """
    n_rows, k_max, b, b2 = blocks.shape
    assert b == b2, blocks.shape
    n, fdim = f.shape
    assert n == n_rows * b, (f.shape, blocks.shape)
    eta = len(coeffs)
    order = len(coeffs[0]) - 1
    assert order >= 1, "need at least order 1 (two coefficients)"
    ft = f_tile or min(fdim, 128)
    assert fdim % ft == 0, (fdim, ft)
    alpha = lmax / 2.0
    kdt = jnp.dtype(krylov_dtype)

    kernel = functools.partial(
        _cheb_union_kernel,
        coeffs=coeffs,
        alpha=alpha,
        n_rows=n_rows,
        k_max=k_max,
        block=b,
        ft=ft,
    )

    grid = (fdim // ft,)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (n_rows, k_max, b, b), lambda fi, cols: (0, 0, 0, 0)
                ),
                pl.BlockSpec((n, ft), lambda fi, cols: (0, fi)),
            ],
            out_specs=pl.BlockSpec((eta, n, ft), lambda fi, cols: (0, 0, fi)),
            scratch_shapes=[
                pltpu.VMEM((n, ft), kdt),
                pltpu.VMEM((n, ft), kdt),
                pltpu.VMEM((eta, n, ft), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((eta, n, fdim), f.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(cols.reshape(-1), blocks, f)
