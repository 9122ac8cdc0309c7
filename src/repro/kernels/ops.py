"""Jit'd public wrapper around the Pallas step kernel.

``cheb_apply_bsr`` runs the union apply as a chain of
``cheb_step_pallas`` calls, one per order, each storing its ``T_k`` to
HBM, with the eq. 11 combine left to XLA. It also picks the kernel's F
tile (``autotune.choose_f_tile``).

Callers should normally go through ``repro.filters.GraphFilter`` with
``backend="bsr"`` rather than this wrapper.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.autotune import choose_f_tile
from repro.kernels.cheb_bsr import cheb_step_pallas
from repro.kernels.ref import BlockCsr, bsr_from_dense

__all__ = [
    "BlockCsr",
    "bsr_from_dense",
    "cheb_apply_bsr",
]


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

    Args:
      blocks/rows/cols: Block-CSR Laplacian (see kernels/ref.py).
      f: (N, F) signal batch (use F >= 8 for MXU efficiency on real TPUs).
      coeffs: (eta, M+1) Chebyshev coefficients.
      lmax: spectrum bound (static).
      f_tile: F-dimension tile; default ``autotune.choose_f_tile``.
      krylov_dtype: dtype the carried ``T_{k-1}``/``T_{k-2}`` buffers
        round-trip through between steps (default: ``f.dtype``). With
        ``"bfloat16"`` each step kernel still combines in f32 and the
        eq. 11 accumulator stays in ``f.dtype``; only the stored Krylov
        vectors are rounded.

    Returns: (eta, N, F).
    """
    if f_tile is None:
        f_tile = choose_f_tile(f.shape[1], blocks.shape[1], f.dtype)
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
