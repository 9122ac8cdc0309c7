"""Jit'd public wrappers around the Pallas kernels.

``cheb_apply_bsr_fused`` is the preferred path: one fused ``pallas_call``
runs the recurrence (eq. 9) *and* the union combine (eq. 11) with all
intermediate ``T_k`` state in VMEM. ``cheb_apply_bsr`` is the stepwise
chain — one ``pallas_call`` per order with the combine left to XLA — kept
as the fallback for working sets that exceed VMEM (see
``repro.kernels.autotune``) and as the fused kernel's parity oracle.

Callers should normally go through ``repro.filters.GraphFilter`` with
``backend="bsr"`` rather than these wrappers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.autotune import select_tiling
from repro.kernels.cheb_bsr import cheb_step_pallas, cheb_union_pallas
from repro.kernels.ref import BlockCsr, bsr_from_dense

__all__ = [
    "BlockCsr",
    "bsr_from_dense",
    "cheb_apply_bsr",
    "cheb_apply_bsr_fused",
]


def cheb_apply_bsr_fused(
    blocks: jax.Array,
    rows: jax.Array,
    cols: jax.Array,
    f: jax.Array,
    coeffs,
    lmax: float,
    *,
    interpret: bool = False,
    f_tile: int | None = None,
    krylov_dtype=None,
) -> jax.Array:
    """``Phi~ f`` via the fused union-combine kernel (one ``pallas_call``).

    Parameters
    ----------
    blocks, rows, cols : jax.Array
        Block-CSR Laplacian (see ``kernels/ref.py``).
    f : jax.Array
        (N, F) signal batch.
    coeffs : array-like
        (eta, M+1) Chebyshev coefficients. Converted to static host
        constants — filters are built once, so this costs one compile per
        filter, and lets the kernel bake the eq. 11 combine weights in.
    lmax : float
        Spectrum bound (static).
    interpret : bool
        Pallas interpret mode (CPU validation path).
    f_tile : int, optional
        F tile override; defaults to the autotune table's choice.
    krylov_dtype : dtype-like, optional
        Krylov (ping/pong) buffer precision inside the kernel; default
        f32. ``"bfloat16"`` halves the Krylov VMEM working set while the
        recurrence math and the eq. 11 accumulators stay f32.

    Returns
    -------
    jax.Array
        (eta, N, F).
    """
    ctup = tuple(
        tuple(float(x) for x in row) for row in np.atleast_2d(np.asarray(coeffs))
    )
    kd = jnp.dtype(krylov_dtype or jnp.float32).name
    if f_tile is None:
        n_tiles, b, _ = blocks.shape
        f_tile = select_tiling(
            f.shape[0], f.shape[1], len(ctup), n_tiles, b, f.dtype,
            krylov_dtype=kd,
        ).f_tile
    return cheb_union_pallas(
        blocks, rows, cols, f,
        coeffs=ctup, lmax=float(lmax), f_tile=f_tile, interpret=interpret,
        krylov_dtype=kd,
    )


@functools.partial(
    jax.jit, static_argnames=("lmax", "interpret", "f_tile", "krylov_dtype")
)
def cheb_apply_bsr(
    blocks: jax.Array,
    rows: jax.Array,
    cols: jax.Array,
    f: jax.Array,
    coeffs: jax.Array,
    lmax: float,
    *,
    interpret: bool = False,
    f_tile: int | None = None,
    krylov_dtype: str | None = None,
) -> jax.Array:
    """``Phi~ f`` with the stepwise Pallas chain (one call per order).

    Prefer ``cheb_apply_bsr_fused`` (or ``GraphFilter`` with
    ``backend="bsr"``) — it avoids materializing each ``T_k`` to HBM. This
    chain remains the large-N fallback and the fused kernel's oracle.

    Args:
      blocks/rows/cols: Block-CSR Laplacian (see kernels/ref.py).
      f: (N, F) signal batch (use F >= 8 for MXU efficiency on real TPUs).
      coeffs: (eta, M+1) Chebyshev coefficients.
      lmax: spectrum bound (static).
      krylov_dtype: dtype the carried ``T_{k-1}``/``T_{k-2}`` buffers
        round-trip through between steps (default: ``f.dtype``). With
        ``"bfloat16"`` each step kernel still combines in f32 and the
        eq. 11 accumulator stays in ``f.dtype``; only the stored Krylov
        vectors are rounded — mirroring the fused kernel's mode.

    Returns: (eta, N, F).
    """
    coeffs = jnp.asarray(coeffs, f.dtype)
    alpha = lmax / 2.0
    step = functools.partial(
        cheb_step_pallas, blocks, rows, cols,
        alpha=alpha, f_tile=f_tile, interpret=interpret,
    )

    t0 = f
    with jax.named_scope("repro.bsr.recurrence"):
        t1 = step(f, f, first=True)
    with jax.named_scope("repro.bsr.combine"):
        acc = (
            0.5 * coeffs[:, 0, None, None] * t0[None]
            + coeffs[:, 1, None, None] * t1[None]
        )

    if coeffs.shape[1] <= 2:
        return acc

    kd = jnp.dtype(krylov_dtype or f.dtype)

    def body(carry, c_k):
        t_prev1, t_prev2, acc = carry
        with jax.named_scope("repro.bsr.recurrence"):
            t_k = step(t_prev1, t_prev2)
        with jax.named_scope("repro.bsr.combine"):
            acc = acc + c_k[:, None, None] * t_k.astype(acc.dtype)[None]
        return (t_k, t_prev1, acc), None

    (_, _, acc), _ = jax.lax.scan(
        body, (t1.astype(kd), t0.astype(kd), acc),
        jnp.swapaxes(coeffs[:, 2:], 0, 1),
    )
    return acc
