"""Shifted-Chebyshev approximation of graph Fourier multipliers.

Implements the paper's Sec. III-C machinery:

* eq. (8)  — Chebyshev coefficients ``c_{j,k}`` of each multiplier ``g_j`` on
  ``[0, lmax]`` via Chebyshev--Gauss quadrature (exact for polynomial
  integrands of the quadrature order),
* eq. (9)  — the two-term recurrence
  ``Tbar_k(L) f = (2/alpha)(L - alpha I) Tbar_{k-1}(L) f - Tbar_{k-2}(L) f``
  evaluated with nothing but matvecs against ``L``,
* eq. (11) — the union combine: all ``eta`` multipliers reuse the *same*
  Krylov sequence ``{Tbar_k(L) f}``; each output is a coefficient-weighted
  sum, so the union costs one recurrence + an ``(eta, M+1)`` combine,
* Sec. IV-C — the Chebyshev product identity
  ``T_k T_k' = (T_{k+k'} + T_{|k-k'|})/2`` used to express ``Phi* Phi`` as a
  single degree-2M filter with coefficients ``d_k``.

The recurrence is written against an abstract ``matvec`` so the same code
runs on a dense Laplacian, the Pallas BSR kernel, or a ``shard_map``-wrapped
distributed matvec with halo exchange (core/distributed.py).
"""

from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "cheb_coefficients",
    "cheb_eval",
    "cheb_eval_joint",
    "cheb_apply",
    "cheb_apply_joint",
    "cheb_apply_krylov",
    "cheb_apply_dense",
    "cheb_adjoint_apply",
    "cheb_adjoint_apply_joint",
    "product_coefficients",
    "gram_coefficients",
    "joint_product_coefficients",
    "joint_gram_coefficients",
    "separable_joint_coefficients",
    "inverse_coefficients",
    "inverse_fixed_point_rate",
]

Matvec = Callable[[jax.Array], jax.Array]


def cheb_coefficients(
    multipliers: Sequence[Callable[[np.ndarray], np.ndarray]],
    order: int,
    lmax: float,
    quad_points: int | None = None,
) -> np.ndarray:
    """Chebyshev coefficients of shifted multipliers — paper eq. (8).

    ``c_{j,k} = (2/pi) \\int_0^pi cos(k th) g_j(alpha (cos th + 1)) dth``
    with ``alpha = lmax / 2``, evaluated by midpoint (Chebyshev--Gauss)
    quadrature at ``quad_points`` nodes.

    Args:
      multipliers: eta callables ``g_j: [0, lmax] -> R`` (numpy-vectorized).
      order: truncation order M (paper: M ~ 20 suffices in practice).
      lmax: (an upper bound on) the largest Laplacian eigenvalue.
      quad_points: quadrature nodes; default ``max(order + 1, 64) * 4``.

    Returns:
      float64 array ``c`` of shape (eta, M+1); ``c[j, 0]`` enters the
      reconstruction with the paper's 1/2 factor (see ``cheb_eval``).
    """
    if order < 1:
        raise ValueError(f"Chebyshev order must be >= 1, got {order}")
    p = quad_points or max(order + 1, 64) * 4
    alpha = lmax / 2.0
    theta = np.pi * (np.arange(p) + 0.5) / p  # Chebyshev-Gauss nodes
    x = alpha * (np.cos(theta) + 1.0)  # mapped to [0, lmax]
    k = np.arange(order + 1)
    basis = np.cos(np.outer(k, theta))  # (M+1, P)
    coeffs = np.stack(
        [(2.0 / p) * (basis @ np.asarray(g(x), dtype=np.float64)) for g in multipliers]
    )
    return coeffs


def cheb_eval(coeffs: np.ndarray, x: np.ndarray, lmax: float) -> np.ndarray:
    """Evaluate truncated shifted-Chebyshev series at scalar points ``x``.

    Reconstruction convention (paper eq. 7):
    ``g(x) ~= c_0 / 2 + sum_{k>=1} c_k Tbar_k(x)``.

    Args:
      coeffs: (eta, M+1) or (M+1,) coefficient array.
      x: points in [0, lmax].

    Returns: (eta, len(x)) (or (len(x),) for 1-D coeffs) evaluations.
    """
    c = np.atleast_2d(np.asarray(coeffs, dtype=np.float64))
    x = np.asarray(x, dtype=np.float64)
    alpha = lmax / 2.0
    y = (x - alpha) / alpha  # shift to [-1, 1]
    t_prev2 = np.ones_like(y)
    t_prev1 = y
    out = 0.5 * c[:, :1] * t_prev2 + (c[:, 1:2] * t_prev1 if c.shape[1] > 1 else 0.0)
    for k in range(2, c.shape[1]):
        t_k = 2.0 * y * t_prev1 - t_prev2
        out = out + c[:, k : k + 1] * t_k
        t_prev2, t_prev1 = t_prev1, t_k
    return out if np.asarray(coeffs).ndim == 2 else out[0]


def cheb_apply(
    matvec: Matvec,
    f: jax.Array,
    coeffs: jax.Array,
    lmax: float | jax.Array,
    *,
    unroll: int = 1,
) -> jax.Array:
    """Apply a union of Chebyshev-approximated multipliers: ``Phi~ f``.

    Runs the shifted recurrence (eq. 9) with ``matvec(v) = L @ v`` and
    combines with the coefficient matrix (eq. 11). The Krylov sequence is
    shared across all eta outputs — the paper's central efficiency claim.

    Args:
      matvec: linear map computing ``L @ v`` for v shaped like ``f``.
        May be a dense matmul, the Pallas BSR kernel, or a distributed
        halo-exchange matvec under shard_map.
      f: input signal(s), shape (N,) or (N, F) for a batch of F signals.
      coeffs: (eta, M+1) Chebyshev coefficients (paper convention; the k=0
        term carries the 1/2 factor internally).
      lmax: spectrum upper bound used to shift the polynomials.
      unroll: lax.scan unroll factor for the recurrence.

    Returns:
      (eta,) + f.shape stacked filter outputs ``[Psi~_1 f, ..., Psi~_eta f]``.
    """
    coeffs = jnp.asarray(coeffs, dtype=f.dtype)
    alpha = jnp.asarray(lmax, dtype=f.dtype) / 2.0
    t0 = f  # Tbar_0(L) f = f
    t1 = (matvec(f) - alpha * f) / alpha  # Tbar_1(L) f = (L - aI) f / a
    # acc_j = c_{j,0}/2 * T0 + c_{j,1} * T1  (+ sum_{k>=2} below)
    acc = _outer(0.5 * coeffs[:, 0], t0) + _outer(coeffs[:, 1], t1)

    if coeffs.shape[1] <= 2:
        return acc

    def step(carry, c_k):
        t_prev1, t_prev2, acc = carry
        t_k = (2.0 / alpha) * (matvec(t_prev1) - alpha * t_prev1) - t_prev2
        acc = acc + _outer(c_k, t_k)
        return (t_k, t_prev1, acc), None

    (_, _, acc), _ = jax.lax.scan(
        step, (t1, t0, acc), jnp.swapaxes(coeffs[:, 2:], 0, 1), unroll=unroll
    )
    return acc


def cheb_apply_krylov(
    matvec: Matvec,
    f: jax.Array,
    coeffs: jax.Array,
    lmax: float | jax.Array,
    *,
    unroll: int = 1,
) -> tuple[jax.Array, jax.Array]:
    """``cheb_apply`` that also returns the Krylov stack ``{Tbar_k(L) f}``.

    The topology-churn path (repro.dynamic) needs the intermediate
    recurrence vectors: after a Laplacian delta ``L' = L + dL``, the
    difference stack ``D_k = Tbar_k(L') f - Tbar_k(L) f`` obeys the same
    shifted recurrence driven by ``dL @ Tbar_{k-1}(L) f``, so keeping the
    stack makes the correction computable on a small induced submatrix
    instead of refiltering from scratch (DESIGN.md Sec. 10).

    Returns:
      ``(out, tk)`` where ``out`` matches ``cheb_apply`` and ``tk`` has
      shape ``(M+1,) + f.shape`` with ``tk[k] = Tbar_k(L) f``.
    """
    coeffs = jnp.asarray(coeffs, dtype=f.dtype)
    alpha = jnp.asarray(lmax, dtype=f.dtype) / 2.0
    t0 = f
    t1 = (matvec(f) - alpha * f) / alpha
    acc = _outer(0.5 * coeffs[:, 0], t0) + _outer(coeffs[:, 1], t1)

    if coeffs.shape[1] <= 2:
        return acc, jnp.stack([t0, t1])

    def step(carry, c_k):
        t_prev1, t_prev2, acc = carry
        t_k = (2.0 / alpha) * (matvec(t_prev1) - alpha * t_prev1) - t_prev2
        acc = acc + _outer(c_k, t_k)
        return (t_k, t_prev1, acc), t_k

    (_, _, acc), ts = jax.lax.scan(
        step, (t1, t0, acc), jnp.swapaxes(coeffs[:, 2:], 0, 1), unroll=unroll
    )
    return acc, jnp.concatenate([jnp.stack([t0, t1]), ts], axis=0)


def _outer(c: jax.Array, t: jax.Array) -> jax.Array:
    """(eta,) x t -> (eta,) + t.shape broadcasted product."""
    return c.reshape(c.shape + (1,) * t.ndim) * t[None]


def cheb_apply_dense(
    laplacian_matrix: jax.Array,
    f: jax.Array,
    coeffs: jax.Array,
    lmax: float | jax.Array,
) -> jax.Array:
    """Convenience wrapper: ``cheb_apply`` with a dense Laplacian."""
    return cheb_apply(lambda v: laplacian_matrix @ v, f, coeffs, lmax)


def cheb_adjoint_apply(
    matvec: Matvec,
    a: jax.Array,
    coeffs: jax.Array,
    lmax: float | jax.Array,
) -> jax.Array:
    """Apply the adjoint ``Phi~* a`` — paper eq. (13).

    ``(Phi~* a)_n = sum_j (c_{j,0}/2 a_j + sum_k c_{j,k} Tbar_k(L) a_j)_n``.

    Because each Tbar_k(L) is symmetric, the adjoint runs the same
    recurrence with the eta input blocks stacked along a trailing axis and
    contracts against the coefficients over (j, k) jointly. Cost matches the
    paper: one recurrence on an (N, eta) block — messages of length eta.

    Args:
      a: (eta, N) or (eta, N, F) stacked coefficient signals.

    Returns: (N,) or (N, F) adjoint output.
    """
    coeffs = jnp.asarray(coeffs, dtype=a.dtype)
    eta = coeffs.shape[0]
    if a.shape[0] != eta:
        raise ValueError(f"adjoint input has {a.shape[0]} blocks, coeffs {eta}")
    # Move the block axis last so matvec sees (N, ...) with batched trailing
    # dims: v (N, [F,] eta).
    v = jnp.moveaxis(a, 0, -1)
    alpha = jnp.asarray(lmax, dtype=a.dtype) / 2.0
    t0 = v
    t1 = (matvec(v) - alpha * v) / alpha
    acc = t0 @ (0.5 * coeffs[:, 0]) + t1 @ coeffs[:, 1]

    if coeffs.shape[1] <= 2:
        return acc

    def step(carry, c_k):
        t_prev1, t_prev2, acc = carry
        t_k = (2.0 / alpha) * (matvec(t_prev1) - alpha * t_prev1) - t_prev2
        return (t_k, t_prev1, acc + t_k @ c_k), None

    (_, _, acc), _ = jax.lax.scan(
        step, (t1, t0, acc), jnp.swapaxes(coeffs[:, 2:], 0, 1)
    )
    return acc


def product_coefficients(c1: np.ndarray, c2: np.ndarray) -> np.ndarray:
    """Coefficients of the product of two Chebyshev series.

    Given series ``p = c1_0/2 + sum c1_k T_k`` and likewise ``q`` (paper
    half-first-coefficient convention), returns ``d`` (same convention,
    length ``len(c1) + len(c2) - 1``) with ``p * q = d_0/2 + sum d_k T_k``,
    using ``T_k T_l = (T_{k+l} + T_{|k-l|}) / 2`` (paper Sec. IV-C).
    """
    a = np.asarray(c1, dtype=np.float64).copy()
    b = np.asarray(c2, dtype=np.float64).copy()
    a[0] *= 0.5
    b[0] *= 0.5  # now p = sum_k a_k T_k with plain coefficients
    m = len(a) + len(b) - 1
    r = np.zeros(m)
    # sum part: T_{k+l}
    r += 0.5 * np.convolve(a, b)
    # difference part: T_{|k-l|}
    for k in range(len(a)):
        for l in range(len(b)):
            r[abs(k - l)] += 0.5 * a[k] * b[l]
    r[0] *= 2.0  # back to half-first-coefficient convention
    return r


def gram_coefficients(coeffs: np.ndarray) -> np.ndarray:
    """Degree-2M coefficients ``d_k`` of ``Phi~* Phi~`` (paper Sec. IV-C).

    ``Phi~* Phi~ = sum_j p_j(L)^2`` where ``p_j`` is the j-th truncated
    series, hence ``d = sum_j product_coefficients(c_j, c_j)``. Applying the
    result with ``cheb_apply`` costs 4M|E| messages as the paper states.
    """
    c = np.atleast_2d(np.asarray(coeffs, dtype=np.float64))
    out = np.zeros(2 * (c.shape[1] - 1) + 1)
    for j in range(c.shape[0]):
        out += product_coefficients(c[j], c[j])
    return out


# ------------------------------------------------------------------------
# Multi-shift (joint) polynomial filters — arXiv:2003.11152 generalization.
#
# A joint filter over an ordered tuple of R commuting shift operators
# (S_1, ..., S_R) is
#
#   P(S_1, ..., S_R) = sum_{k_1..k_R} c[j, k_1, .., k_R]
#                        sigma_{k_1} Tbar_{k_1}(S_1) ... sigma_{k_R} Tbar_{k_R}(S_R)
#
# with the paper's half-first-coefficient convention applied *per axis*
# (sigma_0 = 1/2, sigma_k = 1 otherwise). The canonical instance is the
# time-vertex Cartesian product: S_1 = L_G (x) I acting on the sensor axis
# and S_2 = I (x) L_T on the temporal axis — those commute by construction,
# which is the standing assumption of everything below (the joint operator
# is well-defined and symmetric only for commuting symmetric shifts).
#
# Distributed application stays a *local recurrence per shift*: evaluation
# recurses over shift axes, running the eq. 9 recurrence for shift r and,
# for each Krylov vector Tbar_{k_r}(S_r) v, descending into the remaining
# axes. Matvec counts per shift: count_r = M_r * prod_{s<r} (M_s + 1)
# (shift r's recurrence restarts once per outer Krylov vector), which is
# what the per-shift words accounting in GraphFilter.messages_per_apply
# sums over each shift's own halo plan.
# ------------------------------------------------------------------------


def cheb_apply_joint(
    matvecs: Sequence[Matvec],
    f: jax.Array,
    coeffs: jax.Array,
    lmaxes: Sequence[float],
    *,
    unroll: int = 1,
) -> jax.Array:
    """Apply a joint polynomial of R commuting shifts: ``P(S_1..S_R) f``.

    Args:
      matvecs: R linear maps, ``matvecs[r](v) = S_r @ v`` for v shaped
        like ``f`` (dense matmuls, Block-CSR kernels, or halo-exchange
        matvecs — each shift may run on its own exchange plan).
      f: input signal(s), shape (N,) or (N, F).
      coeffs: (eta, M_1+1, ..., M_R+1) joint coefficient tensor.
      lmaxes: per-shift spectrum upper bounds.

    Returns:
      (eta,) + f.shape stacked joint filter outputs. For R = 1 this is
      exactly ``cheb_apply``.
    """
    n_shifts = len(matvecs)
    coeffs = jnp.asarray(coeffs, dtype=f.dtype)
    if coeffs.ndim != n_shifts + 1:
        raise ValueError(
            f"joint coeffs must have ndim R+1 = {n_shifts + 1} "
            f"(eta leading), got shape {coeffs.shape}"
        )
    if len(lmaxes) != n_shifts:
        raise ValueError(f"{len(lmaxes)} lmaxes for {n_shifts} shifts")
    if n_shifts == 1:
        return cheb_apply(matvecs[0], f, coeffs, lmaxes[0], unroll=unroll)
    # Transpose eta to trailing so recursion peels leading shift axes.
    ct = jnp.moveaxis(coeffs, 0, -1)  # (M_1+1, ..., M_R+1, eta)

    def rec(v: jax.Array, c: jax.Array, level: int) -> jax.Array:
        if level == n_shifts - 1:  # innermost shift: plain union apply
            return cheb_apply(
                matvecs[level], v, jnp.moveaxis(c, -1, 0),
                lmaxes[level], unroll=unroll,
            )
        mv = matvecs[level]
        alpha = jnp.asarray(lmaxes[level], dtype=f.dtype) / 2.0
        t0 = v
        t1 = (mv(v) - alpha * v) / alpha
        # per-axis half convention: the k=0 Krylov vector enters with 1/2
        acc = 0.5 * rec(t0, c[0], level + 1) + rec(t1, c[1], level + 1)
        if c.shape[0] <= 2:
            return acc

        def step(carry, c_k):
            t_prev1, t_prev2, acc = carry
            t_k = (2.0 / alpha) * (mv(t_prev1) - alpha * t_prev1) - t_prev2
            acc = acc + rec(t_k, c_k, level + 1)
            return (t_k, t_prev1, acc), None

        (_, _, acc), _ = jax.lax.scan(
            step, (t1, t0, acc), c[2:], unroll=unroll
        )
        return acc

    return rec(f, ct, 0)


def cheb_adjoint_apply_joint(
    matvecs: Sequence[Matvec],
    a: jax.Array,
    coeffs: jax.Array,
    lmaxes: Sequence[float],
) -> jax.Array:
    """Joint adjoint ``P* a`` for ``a`` shaped (eta,) + signal.shape.

    Commuting symmetric shifts make each joint term symmetric, so the
    adjoint runs the same per-axis recurrences with the eta blocks stacked
    along a trailing axis (paper Sec. IV-B pattern) and contracts against
    the coefficients at the innermost level.
    """
    n_shifts = len(matvecs)
    coeffs = jnp.asarray(coeffs, dtype=a.dtype)
    if coeffs.ndim != n_shifts + 1:
        raise ValueError(
            f"joint coeffs must have ndim R+1 = {n_shifts + 1}, "
            f"got shape {coeffs.shape}"
        )
    if a.shape[0] != coeffs.shape[0]:
        raise ValueError(
            f"adjoint input has {a.shape[0]} blocks, coeffs {coeffs.shape[0]}"
        )
    if n_shifts == 1:
        return cheb_adjoint_apply(matvecs[0], a, coeffs, lmaxes[0])
    ct = jnp.moveaxis(coeffs, 0, -1)  # (M_1+1, ..., M_R+1, eta)
    v0 = jnp.moveaxis(a, 0, -1)  # (N, [F,] eta)

    def rec(v: jax.Array, c: jax.Array, level: int) -> jax.Array:
        if level == n_shifts - 1:
            return cheb_adjoint_apply(
                matvecs[level], jnp.moveaxis(v, -1, 0),
                jnp.moveaxis(c, -1, 0), lmaxes[level],
            )
        mv = matvecs[level]
        alpha = jnp.asarray(lmaxes[level], dtype=a.dtype) / 2.0
        t0 = v
        t1 = (mv(v) - alpha * v) / alpha
        acc = 0.5 * rec(t0, c[0], level + 1) + rec(t1, c[1], level + 1)
        if c.shape[0] <= 2:
            return acc

        def step(carry, c_k):
            t_prev1, t_prev2, acc = carry
            t_k = (2.0 / alpha) * (mv(t_prev1) - alpha * t_prev1) - t_prev2
            acc = acc + rec(t_k, c_k, level + 1)
            return (t_k, t_prev1, acc), None

        (_, _, acc), _ = jax.lax.scan(step, (t1, t0, acc), c[2:])
        return acc

    return rec(v0, ct, 0)


def cheb_eval_joint(
    coeffs: np.ndarray, xs: Sequence[np.ndarray], lmaxes: Sequence[float]
) -> np.ndarray:
    """Evaluate a joint series on the tensor grid ``xs[0] x ... x xs[R-1]``.

    Args:
      coeffs: (eta, M_1+1, ..., M_R+1) joint coefficient tensor.
      xs: per-axis evaluation points, each within [0, lmaxes[r]].

    Returns: (eta, len(xs[0]), ..., len(xs[R-1])) evaluations with the
    per-axis half-first-coefficient convention.
    """
    c = np.asarray(coeffs, dtype=np.float64)
    n_shifts = len(xs)
    if c.ndim != n_shifts + 1:
        raise ValueError(
            f"joint coeffs must have ndim R+1 = {n_shifts + 1}, "
            f"got shape {c.shape}"
        )
    out = c
    for r in range(n_shifts):
        basis = _cheb_basis(c.shape[1 + r] - 1, xs[r], lmaxes[r])
        basis[0] *= 0.5  # half convention on this axis
        # contract axis 1 (the current leading shift axis); the grid axis
        # lands at the end, so axis order is preserved overall.
        out = np.tensordot(out, basis, axes=[[1], [0]])
    return out


def _cheb_basis(order: int, x: np.ndarray, lmax: float) -> np.ndarray:
    """(M+1, len(x)) matrix of shifted Chebyshev values ``Tbar_k(x)``."""
    x = np.asarray(x, dtype=np.float64)
    alpha = lmax / 2.0
    y = (x - alpha) / alpha
    basis = np.empty((order + 1, len(x)))
    basis[0] = 1.0
    if order >= 1:
        basis[1] = y
    for k in range(2, order + 1):
        basis[k] = 2.0 * y * basis[k - 1] - basis[k - 2]
    return basis


def _halve_axis0(c: np.ndarray, axes: Sequence[int]) -> np.ndarray:
    """Half-convention -> plain coefficients along the given axes."""
    c = np.array(c, dtype=np.float64)
    for ax in axes:
        sl = [slice(None)] * c.ndim
        sl[ax] = 0
        c[tuple(sl)] *= 0.5
    return c


def joint_product_coefficients(c1: np.ndarray, c2: np.ndarray) -> np.ndarray:
    """Joint-tensor analog of :func:`product_coefficients`.

    ``c1``/``c2`` are (M_1+1, ..., M_R+1) coefficient tensors of single
    multipliers (half convention per axis); returns the
    (2M_1+1, ..., 2M_R+1)-shaped coefficients of their product, applying
    ``T_k T_l = (T_{k+l} + T_{|k-l|}) / 2`` independently on every axis.
    """
    a = _halve_axis0(np.atleast_1d(c1), range(np.ndim(c1)))
    b = _halve_axis0(np.atleast_1d(c2), range(np.ndim(c2)))
    n_shifts = a.ndim
    if b.ndim != n_shifts:
        raise ValueError(f"rank mismatch: {a.shape} vs {b.shape}")
    # Outer tensor over (k_1..k_R, l_1..l_R), then fold each (k_r, l_r)
    # pair into one m_r axis with the 1-D product identity.
    t = np.multiply.outer(a, b)
    for r in range(n_shifts):
        # After r folds, t has axes (m_1..m_r, k_{r+1}..k_R, l_{r+1}..l_R);
        # the current k axis is at r, the matching l axis at n_shifts.
        t = np.moveaxis(t, (r, n_shifts), (0, 1))
        k_dim, l_dim = t.shape[0], t.shape[1]
        folded = np.zeros((k_dim + l_dim - 1,) + t.shape[2:])
        for k in range(k_dim):
            for l in range(l_dim):
                folded[k + l] += 0.5 * t[k, l]
                folded[abs(k - l)] += 0.5 * t[k, l]
        t = np.moveaxis(folded, 0, r)
    # plain -> half convention on every axis
    out = t
    for ax in range(n_shifts):
        sl = [slice(None)] * out.ndim
        sl[ax] = 0
        out[tuple(sl)] *= 2.0
    return out


def joint_gram_coefficients(coeffs: np.ndarray) -> np.ndarray:
    """Joint coefficients of ``P* P = sum_j p_j(S_1..S_R)^2``.

    ``coeffs`` is (eta, M_1+1, ..., M_R+1); the result is
    (2M_1+1, ..., 2M_R+1). For R = 1 this reduces exactly to
    :func:`gram_coefficients`.
    """
    c = np.asarray(coeffs, dtype=np.float64)
    out = np.zeros(tuple(2 * (m - 1) + 1 for m in c.shape[1:]))
    for j in range(c.shape[0]):
        out += joint_product_coefficients(c[j], c[j])
    return out


def separable_joint_coefficients(
    factors: Sequence[np.ndarray],
) -> np.ndarray:
    """Joint tensor of a separable multiplier ``g(x_1..x_R) = prod g_r(x_r)``.

    Each factor is the (eta, M_r+1) or (M_r+1,) 1-D half-convention series
    of ``g_r``; the outer product of half-convention series IS the
    half-convention joint tensor (sigma factors multiply per axis).
    Multi-multiplier factors must share eta; the output is
    (eta, M_1+1, ..., M_R+1).
    """
    mats = [np.atleast_2d(np.asarray(f, dtype=np.float64)) for f in factors]
    eta = max(m.shape[0] for m in mats)
    for m in mats:
        if m.shape[0] not in (1, eta):
            raise ValueError("factors must share eta (or be single)")
    out = None
    for m in mats:
        m = np.broadcast_to(m, (eta,) + m.shape[1:])
        if out is None:
            out = m
        else:
            # per-j outer product over the shift axes
            out = np.einsum("j...,jk->j...k", out, m)
    return out


def inverse_coefficients(
    h_coeffs: np.ndarray,
    lmax: float | Sequence[float],
    order: int | Sequence[int],
    *,
    reg: float = 0.0,
    quad_points: int | None = None,
) -> np.ndarray:
    """Low-order Chebyshev fit of ``q(lambda) ~= 1 / (h(lambda) + reg)``.

    The inverse-filtering core (arXiv:2504.14341): ``h`` is given by its
    own Chebyshev series (typically a filter's ``gram_coeffs``), and the
    returned order-K series ``q`` approximates its regularized reciprocal
    on the spectral domain — used as a polynomial preconditioner for CG
    and as the standalone fixed-point iteration
    ``x <- x + q(L) (b - (h(L) + reg) x)``, whose linear rate is
    :func:`inverse_fixed_point_rate`.

    Single-shift: ``h_coeffs`` is (2M+1,), ``lmax``/``order`` scalars, and
    the result is an (K+1,) series fit by Chebyshev--Gauss quadrature.
    Multi-shift: ``h_coeffs`` is a joint (2M_1+1, ..., 2M_R+1) tensor,
    ``lmax``/``order`` sequences, and the fit is the per-axis tensor
    quadrature returning a (K_1+1, ..., K_R+1) joint series.

    ``h + reg`` must be positive on the whole domain (it is for any Gram
    series with reg > 0 up to the approximation floor); a nonpositive
    minimum raises rather than returning a garbage fit.
    """
    h = np.asarray(h_coeffs, dtype=np.float64)
    scalar = np.isscalar(lmax) or np.ndim(lmax) == 0
    lmaxes = [float(lmax)] if scalar else [float(v) for v in lmax]
    orders = [int(order)] if scalar else [int(v) for v in order]
    if h.ndim != len(lmaxes) or len(orders) != len(lmaxes):
        raise ValueError(
            f"h ndim {h.ndim} vs {len(lmaxes)} lmaxes / {len(orders)} orders"
        )
    ps = [
        quad_points or max(k + 1, 64) * 4 for k in orders
    ]
    thetas = [np.pi * (np.arange(p) + 0.5) / p for p in ps]
    xs = [
        (lm / 2.0) * (np.cos(th) + 1.0) for lm, th in zip(lmaxes, thetas)
    ]
    hv = cheb_eval_joint(h[None], xs, lmaxes)[0]
    denom = hv + reg
    if float(denom.min()) <= 0.0:
        raise ValueError(
            f"h + reg not positive on the domain (min {float(denom.min()):.3e});"
            " raise reg= or check the series"
        )
    c = 1.0 / denom
    for r in range(len(lmaxes)):
        basis = np.cos(np.outer(np.arange(orders[r] + 1), thetas[r]))
        c = np.tensordot(c, basis, axes=[[0], [1]]) * (2.0 / ps[r])
    return c if not scalar else np.asarray(c)


def inverse_fixed_point_rate(
    q_coeffs: np.ndarray,
    h_coeffs: np.ndarray,
    lmax: float | Sequence[float],
    *,
    reg: float = 0.0,
    grid: int = 2048,
) -> float:
    """Sup-norm contraction factor ``max |1 - q(x)(h(x) + reg)|``.

    The fixed-point iteration ``x <- x + q(L) r`` converges linearly at
    this rate (error multiplies by it each sweep); values >= 1 mean the
    fit order is too low for the given ``h`` / ``reg``.
    """
    q = np.asarray(q_coeffs, dtype=np.float64)
    h = np.asarray(h_coeffs, dtype=np.float64)
    scalar = np.isscalar(lmax) or np.ndim(lmax) == 0
    lmaxes = [float(lmax)] if scalar else [float(v) for v in lmax]
    n_pts = max(64, int(round(grid ** (1.0 / len(lmaxes)))))
    xs = [np.linspace(0.0, lm, n_pts) for lm in lmaxes]
    qv = cheb_eval_joint(q[None], xs, lmaxes)[0]
    hv = cheb_eval_joint(h[None], xs, lmaxes)[0]
    return float(np.max(np.abs(1.0 - qv * (hv + reg))))
