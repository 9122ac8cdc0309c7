"""Paper Sec. V applications: smoothing, Tikhonov denoising, SGWT-lasso
denoising, and semi-supervised classification.

Every routine is built on :class:`repro.filters.GraphFilter`, so it runs
unchanged on any registered backend — dense, Pallas Block-CSR, or the
``shard_map``-distributed meshes — the paper's point being that the *same*
Chebyshev recurrence implements all deployment modes. Each takes a
:class:`~repro.core.graph.SensorGraph`; pass ``backend="..."`` to choose
the execution substrate. (The PR-1 ``matvec``-closure calling convention
was removed; callers holding only an ``L @ v`` closure build a
``GraphFilter`` without a graph and use the ``"matvec"`` backend
directly.)
"""

from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import multipliers as mult
from repro.core.graph import SensorGraph
from repro.filters import GraphFilter
from repro.solvers import (
    GramProblem,
    LassoProblem,
    SolveResult,
    conjugate_gradient,
    solve,
    wiener,
)

__all__ = [
    "smooth_heat",
    "denoise_tikhonov",
    "wavelet_denoise_ista",
    "denoise_wiener",
    "inverse_filter",
    "ssl_classify",
]


def _as_filter(g: SensorGraph, bank, order: int, lmax: float,
               backend: str | None, opts: dict):
    """Build a GraphFilter + resolved (backend, opts) for a graph."""
    if not isinstance(g, SensorGraph):
        raise TypeError(
            f"expected a SensorGraph, got {type(g).__name__}; the legacy "
            "matvec-closure convention was removed — build a GraphFilter "
            "and use backend='matvec' directly"
        )
    filt = GraphFilter.from_multipliers(bank, order, graph=g, lmax=lmax)
    return filt, backend or "dense", opts


def smooth_heat(
    graph: SensorGraph,
    y: jax.Array,
    lmax: float,
    t: float = 1.0,
    order: int = 20,
    *,
    backend: str | None = None,
    **opts,
) -> jax.Array:
    """Distributed smoothing (Sec. V-A): ``H~_t y`` with ``g = exp(-t x)``.

    Parameters
    ----------
    graph : SensorGraph
        The graph to smooth on (any backend).
    y : jax.Array
        (N,) or (N, F) signal to smooth.
    lmax : float
        Spectrum upper bound.
    t, order : float, int
        Heat-kernel time and Chebyshev order.
    backend : str, optional
        ``GraphFilter`` backend (default ``dense`` for graphs).
    """
    filt, be, opts = _as_filter(
        graph, [mult.heat(t)], order, lmax, backend, opts)
    return filt.apply(y, backend=be, **opts)[0]


def denoise_tikhonov(
    graph: SensorGraph,
    y: jax.Array,
    lmax: float,
    tau: float = 1.0,
    r: int = 1,
    order: int = 20,
    *,
    backend: str | None = None,
    **opts,
) -> jax.Array:
    """Distributed denoising (Sec. V-B, Prop. 1): ``R~ y`` with
    ``g(x) = tau / (tau + 2 x^r)`` — the closed-form minimizer of
    ``tau/2 ||f - y||^2 + f^T L^r f`` applied via Algorithm 1."""
    filt, be, opts = _as_filter(
        graph, [mult.tikhonov(tau, r)], order, lmax, backend, opts)
    return filt.apply(y, backend=be, **opts)[0]


def ssl_classify(
    graph: SensorGraph,
    labels: jax.Array,
    lmax: float,
    tau: float = 1.0,
    r: int = 1,
    order: int = 20,
    *,
    backend: str | None = None,
    **opts,
) -> jax.Array:
    """Distributed binary SSL (Sec. V-B end): labelled nodes carry +-1,
    unlabelled carry 0; every node outputs ``sign((R~ y)_n)``."""
    scores = denoise_tikhonov(
        graph, labels, lmax, tau, r, order, backend=backend, **opts)
    return jnp.where(scores >= 0.0, 1.0, -1.0)


def wavelet_denoise_ista(
    graph: SensorGraph,
    y: jax.Array,
    lmax: float,
    *,
    n_scales: int = 4,
    order: int = 24,
    mu: float | jax.Array = 1.0,
    n_iters: int = 50,
    step: float | None = None,
    method: str = "ista",
    tol: float | None = None,
    backend: str | None = None,
    full_output: bool = False,
    **opts,
) -> tuple[jax.Array, jax.Array] | SolveResult:
    """Distributed SGWT-lasso denoising (Sec. V-C).

    Solves ``argmin_a 1/2 ||y - W~* a||^2 + ||a||_{1,mu}`` where ``W~`` is
    the Chebyshev-approximated spectral graph wavelet transform (a union
    with eta = n_scales + 1), by delegating to :mod:`repro.solvers`:
    ``method="ista"`` is the paper's eq. 21 iteration, ``method="fista"``
    adds Nesterov momentum — same per-iteration communication (one adjoint
    of length-eta messages + one forward of length-1), O(1/k^2) instead of
    O(1/k) objective decay. Whether the loop compiles to one
    ``lax.scan``/``while_loop`` or runs on host follows the backend's
    ``traceable`` capability flag.

    ``tol`` enables early stopping on the relative objective change;
    ``full_output=True`` returns the :class:`~repro.solvers.SolveResult`
    (iteration count, objective history, message accounting) instead of
    the legacy ``(denoised_signal, wavelet_coefficients)`` pair.
    """
    bank = mult.sgwt_filter_bank(lmax, n_scales=n_scales)
    filt, be, opts = _as_filter(graph, bank, order, lmax,
                                backend, opts)
    problem = LassoProblem(filt=filt, y=y, mu=mu, step=step)
    res = solve(problem, method=method, n_iters=n_iters, tol=tol,
                backend=be, **opts)
    if full_output:
        return res
    return res.x, res.aux


def denoise_wiener(
    graph: SensorGraph,
    y: jax.Array,
    lmax: float,
    *,
    noise_power: float = 0.25,
    psd: Callable[[np.ndarray], np.ndarray] | None = None,
    order: int = 20,
    n_iters: int = 50,
    tol: float | None = 1e-6,
    backend: str | None = None,
    full_output: bool = False,
    **opts,
) -> jax.Array | SolveResult:
    """Iterative graph Wiener denoising (arXiv:2205.04019).

    Models the clean signal as zero-mean with spectral power density
    ``psd(lambda)`` (default: the smooth low-pass prior ``1/(1+x)^2``) and
    the noise as white with power ``noise_power``; the Wiener estimate

        ``x_hat = h(L) (h(L) + sigma^2 I)^{-1} y``,  h = psd,

    is computed *without* any eigendecomposition: ``h(L)`` is the Gram
    operator of the ``sqrt(psd)`` filter, inverted by distributed CG —
    every iteration one degree-2M Chebyshev filter (Sec. IV-C).
    """
    if psd is None:
        def psd(x):
            return 1.0 / (1.0 + np.asarray(x, np.float64)) ** 2

    def sqrt_psd(x):
        return np.sqrt(np.maximum(psd(x), 0.0))

    filt, be, opts = _as_filter(graph, [sqrt_psd], order, lmax,
                                backend, opts)
    res = wiener(filt, y, noise_power, n_iters=n_iters, tol=tol,
                 backend=be, **opts)
    return res if full_output else res.x


def inverse_filter(
    graph: SensorGraph,
    observations: jax.Array,
    lmax: float,
    *,
    bank: Sequence[Callable[[np.ndarray], np.ndarray]],
    order: int = 20,
    reg: float = 0.0,
    n_iters: int = 50,
    tol: float | None = 1e-6,
    backend: str | None = None,
    full_output: bool = False,
    **opts,
) -> jax.Array | SolveResult:
    """Distributed inverse filtering (arXiv:2003.11152).

    Given observations ``b = Phi~ x`` — the (eta,) + signal.shape stacked
    outputs of the multiplier union ``bank`` — recover ``x`` as the
    least-squares solution of the normal equations
    ``(Phi~* Phi~ + reg I) x = Phi~* b`` via CG on the Gram operator
    (``reg > 0`` stabilizes ill-conditioned banks). All compute is
    Chebyshev recurrences: one adjoint up front, one degree-2M gram
    filter per iteration.
    """
    filt, be, opts = _as_filter(graph, list(bank), order, lmax,
                                backend, opts)
    rhs = filt.adjoint(jnp.asarray(observations), backend=be, **opts)
    res = conjugate_gradient(
        GramProblem(filt=filt, b=rhs, reg=reg),
        n_iters=n_iters, tol=tol, backend=be, **opts)
    return res if full_output else res.x
