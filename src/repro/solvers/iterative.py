"""The solvers: ISTA, FISTA, conjugate gradient, Wiener reconstruction.

All four run entirely on :class:`repro.filters.GraphFilter` calls — one
forward and/or adjoint (lasso) or one ``gram`` (CG) per iteration — so they
execute unchanged on every registered backend, and their communication cost
is exactly the paper's accounting for those primitives. Loop mechanics
(compiled scan / while_loop vs host loop) are chosen from the backend's
``traceable`` capability by :mod:`repro.solvers.loops`.

Solver selection guide (DESIGN.md Sec. 7):

* ``ista``  — paper eq. 21 verbatim; the reference iteration.
* ``fista`` — same per-iteration communication (one forward + one adjoint),
  Nesterov momentum gives O(1/k^2) objective decay vs ISTA's O(1/k):
  strictly fewer iterations to a given objective, hence strictly fewer
  messages — on a radio network that is the whole game.
* ``conjugate_gradient`` — inverse filtering on the Gram operator
  (arXiv:2003.11152); one degree-2M ``gram`` filter per iteration.
* ``wiener`` — Wiener/Tikhonov reconstruction (arXiv:2205.04019):
  ``x = G (G + sigma^2 I)^{-1} y`` with ``G = Phi~* Phi~``, via CG.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.filters import GraphFilter, backend_is_traceable
from repro.solvers.api import GramProblem, LassoProblem, SolveResult
from repro.solvers.loops import iterate

__all__ = [
    "ista",
    "fista",
    "conjugate_gradient",
    "wiener",
    "solve",
    "lasso_panel_program",
]


def _lasso_setup(problem: LassoProblem, backend: str, opts: dict):
    filt, y = problem.filt, jnp.asarray(problem.y)
    tau = jnp.asarray(problem.step_size(), y.dtype)
    muv = problem.mu_vector()
    thresh = muv * tau

    def fwd(v):
        return filt.apply(v, backend=backend, **opts)

    def adj(a):
        return filt.adjoint(a, backend=backend, **opts)

    def soft(z):
        return jnp.sign(z) * jnp.maximum(jnp.abs(z) - thresh, 0.0)

    def l1(a):
        return jnp.sum(muv * jnp.abs(a))

    return y, tau, fwd, adj, soft, l1


def _ista_machine(y, tau, fwd, adj, soft, l1):
    """ISTA as (step, init, final): the eq. 21 update factored so the
    host-driven solvers and the compiled panel program share one copy of
    the math."""

    def step(state):
        a, obj_prev = state
        r = y - adj(a)
        obj = 0.5 * jnp.sum(r * r) + l1(a)
        a_new = soft(a + tau * fwd(r))
        stop = jnp.abs(obj_prev - obj) / jnp.maximum(jnp.abs(obj), 1.0)
        return (a_new, obj), (obj, stop)

    def init(a0):
        return (a0, jnp.asarray(jnp.inf, y.dtype))

    def final(state):
        return state[0]

    return step, init, final


def _fista_machine(y, tau, fwd, adj, soft, l1):
    """FISTA as (step, init, final) — see :func:`_ista_machine`."""

    def step(state):
        a_prev, z, t, obj_prev = state
        r = y - adj(z)
        obj = 0.5 * jnp.sum(r * r) + l1(z)
        a = soft(z + tau * fwd(r))
        t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        z_new = a + ((t - 1.0) / t_new) * (a - a_prev)
        stop = jnp.abs(obj_prev - obj) / jnp.maximum(jnp.abs(obj), 1.0)
        return (a, z_new, t_new, obj), (obj, stop)

    def init(a0):
        return (a0, a0, jnp.asarray(1.0, y.dtype), jnp.asarray(jnp.inf, y.dtype))

    def final(state):
        return state[0]

    return step, init, final


_LASSO_MACHINES = {"ista": _ista_machine, "fista": _fista_machine}


def _lasso_result(problem, state_a, hist, k, conv, method, backend, opts):
    xhat = problem.filt.adjoint(state_a, backend=backend, **opts)
    return SolveResult(
        x=xhat,
        aux=state_a,
        history=hist,
        iterations=k,
        converged=conv,
        method=method,
        backend=backend,
        messages_per_iteration=problem.messages_per_iteration(backend, **opts),
    )


def ista(
    problem: LassoProblem,
    *,
    a0: jax.Array | None = None,
    n_iters: int = 50,
    tol: float | None = None,
    backend: str = "dense",
    **opts,
) -> SolveResult:
    """Iterative soft thresholding (paper eq. 21).

    ``a <- S_{mu tau}(a + tau Phi~ (y - Phi~* a))``, started at
    ``a0 = Phi~ y`` by default (the paper stores the first forward
    transform "for future iterations"). Pass ``a0=`` to warm-start from a
    previous solution instead — the streaming lane seeds each frame with
    the last frame's coefficients, cutting iterations-to-tolerance on
    slowly varying scenes (DESIGN.md Sec. 8). History records the
    objective of each incoming iterate (computed from the residual the
    update needs anyway — no extra filter calls); ``tol`` stops on its
    relative change.
    """
    y, tau, fwd, adj, soft, l1 = _lasso_setup(problem, backend, opts)
    a0 = fwd(y) if a0 is None else jnp.asarray(a0, y.dtype)

    step, init, final = _ista_machine(y, tau, fwd, adj, soft, l1)
    state, hist, k, conv = iterate(
        step, init(a0), n_iters=n_iters, tol=tol, traceable=backend_is_traceable(backend)
    )
    return _lasso_result(problem, final(state), hist, k, conv, "ista", backend, opts)


def fista(
    problem: LassoProblem,
    *,
    a0: jax.Array | None = None,
    n_iters: int = 50,
    tol: float | None = None,
    backend: str = "dense",
    **opts,
) -> SolveResult:
    """FISTA (Beck & Teboulle 2009): ISTA + Nesterov momentum.

    Identical per-iteration communication to :func:`ista` — one forward
    (length-1 messages) and one adjoint (length-eta) — but O(1/k^2)
    objective decay, so the same objective is reached in far fewer
    iterations (and therefore far fewer network words). The proximal step
    is taken at the extrapolated point ``z``; history records the
    objective at ``z`` (free, from the residual the gradient needs).
    ``a0=`` warm-starts from a previous solution (momentum restarts at
    t=1, the safe choice for a shifted objective).
    """
    y, tau, fwd, adj, soft, l1 = _lasso_setup(problem, backend, opts)
    a0 = fwd(y) if a0 is None else jnp.asarray(a0, y.dtype)

    step, init, final = _fista_machine(y, tau, fwd, adj, soft, l1)
    state, hist, k, conv = iterate(
        step, init(a0), n_iters=n_iters, tol=tol, traceable=backend_is_traceable(backend)
    )
    return _lasso_result(problem, final(state), hist, k, conv, "fista", backend, opts)


def _colsum(u: jax.Array, v: jax.Array) -> jax.Array:
    """Per-column inner product: scalar for (N,), (F,) for (N, F)."""
    return jnp.sum(u * v, axis=0)


def conjugate_gradient(
    problem: GramProblem,
    *,
    x0: jax.Array | None = None,
    n_iters: int = 50,
    tol: float | None = 1e-6,
    backend: str = "dense",
    preconditioner=None,
    **opts,
) -> SolveResult:
    """CG on ``(Phi~* Phi~ + reg I) x = b`` — distributed inverse
    filtering (arXiv:2003.11152).

    Each iteration is ONE ``GraphFilter.gram`` call (a single degree-2M
    filter, Sec. IV-C) — 4M|E| radio-model words, half the cost of
    composing ``adjoint(apply(.))``. Panel right-hand sides (N, F) are F
    independent systems: step sizes are computed per column, and the
    tolerance applies to the worst column's relative residual. History
    records that worst-column residual norm.

    ``preconditioner=`` enables PCG: a callable ``r -> M^{-1} r`` applied
    once per iteration — canonically a
    :class:`repro.solvers.ChebyshevPreconditioner` (built by
    :func:`repro.solvers.cheb_preconditioner`), which applies a low-order
    polynomial fit of ``1/(h + reg)`` and therefore clusters the
    preconditioned spectrum around 1, collapsing iterations-to-tolerance.
    When the preconditioner declares per-shift ``orders`` its words are
    added to ``messages_per_iteration``, so ``messages_total`` compares
    fairly against plain CG. The convergence/tolerance bookkeeping stays
    in the TRUE residual ``r`` (not the preconditioned one), so histories
    of plain and preconditioned runs are directly comparable.
    """
    b = jnp.asarray(problem.b)
    mv = problem.operator(backend, **opts)
    x = jnp.zeros_like(b) if x0 is None else jnp.asarray(x0, b.dtype)
    r = b - mv(x)
    bnorm = jnp.maximum(jnp.sqrt(_colsum(b, b)), 1e-30)
    eps = jnp.asarray(1e-30, b.dtype)
    precond = preconditioner if preconditioner is not None else (lambda v: v)

    def step(state):
        x, r, p, rz = state
        ap = mv(p)
        alpha = rz / jnp.maximum(_colsum(p, ap), eps)
        x = x + alpha * p
        r = r - alpha * ap
        z = precond(r)
        rz_new = _colsum(r, z)
        p = z + (rz_new / jnp.maximum(rz, eps)) * p
        rs_new = _colsum(r, r)
        rel = jnp.sqrt(rs_new) / bnorm
        return (x, r, p, rz_new), (jnp.max(jnp.sqrt(rs_new)), jnp.max(rel))

    z0 = precond(r)
    init = (x, r, z0, _colsum(r, z0))
    (x, _, _, _), hist, k, conv = iterate(
        step, init, n_iters=n_iters, tol=tol, traceable=backend_is_traceable(backend)
    )
    words = problem.messages_per_iteration(backend, **opts)
    pre_orders = getattr(preconditioner, "orders", None)
    if pre_orders is not None:
        words += problem.filt.messages_per_apply(orders=pre_orders, backend=backend, **opts)
    return SolveResult(
        x=x,
        aux=None,
        history=hist,
        iterations=k,
        converged=conv,
        method="cg" if preconditioner is None else "pcg",
        backend=backend,
        messages_per_iteration=words,
    )


def wiener(
    filt: GraphFilter,
    y: jax.Array,
    noise_power: float,
    *,
    x0: jax.Array | None = None,
    n_iters: int = 50,
    tol: float | None = 1e-6,
    backend: str = "dense",
    **opts,
) -> SolveResult:
    """Graph Wiener reconstruction (arXiv:2205.04019), fully iterative.

    With signal PSD ``h`` and ``filt`` built from ``sqrt(h)`` (so the Gram
    operator is ``G = h(L)``), the Wiener estimate of ``x`` from
    ``y = x + n``, ``n ~ N(0, sigma^2 I)``, is

        ``x_hat = G (G + sigma^2 I)^{-1} y``

    — one CG solve on the regularized Gram system plus one final ``gram``
    apply, i.e. nothing but Chebyshev recurrences on every backend.
    Returns the estimate in ``x`` and the latent ``(G + sigma^2)^{-1} y``
    in ``aux``. ``x0=`` warm-starts the CG solve from a previous latent
    (the streaming lane seeds each frame with the last frame's ``aux``).
    """
    res = conjugate_gradient(
        GramProblem(filt=filt, b=y, reg=float(noise_power)),
        x0=x0,
        n_iters=n_iters,
        tol=tol,
        backend=backend,
        **opts,
    )
    xhat = filt.gram(res.x, backend=backend, **opts)
    return dataclasses.replace(res, x=xhat, aux=res.x, method="wiener")


def lasso_panel_program(
    filt: GraphFilter,
    *,
    method: str = "fista",
    mu: float | jax.Array = 1.0,
    step: float | None = None,
    n_iters: int = 40,
    backend: str = "dense",
    **opts,
):
    """Build a pure whole-solve panel program — ONE jit-able function.

    Returns ``y (N, F) -> (x, a, history)`` running the complete
    fixed-budget ``method`` lasso solve: ``x`` is the (N, F) denoised
    panel, ``a`` the (eta, N, F) coefficients, ``history`` the
    (n_iters,) float32 panel-summed objective trace. Unlike
    :func:`ista`/:func:`fista` — which drive ``lax.scan`` eagerly and
    re-trace on every call — the returned function stages pure jax end
    to end, so a serving engine can wrap it in ``jax.jit`` once per
    panel-width bucket and answer every subsequent panel from the
    compiled-program cache (DESIGN.md Sec. 9).

    Requires a ``traceable`` backend and a fixed iteration budget:
    tolerance-based early exit yields data-dependent iteration counts,
    which cannot live inside one compiled program.
    """
    if not backend_is_traceable(backend):
        raise ValueError(
            f"lasso_panel_program needs a traceable backend; {backend!r} "
            "stages host transfers (use ista/fista's host loop instead)"
        )
    try:
        machine = _LASSO_MACHINES[method]
    except KeyError:
        raise ValueError(
            f"unknown lasso method {method!r}; use 'ista' or 'fista'"
        ) from None
    # Prepare backend state eagerly so the first traced call closes over
    # concrete operands instead of baking preparation into the trace.
    filt.prepare_backend(backend, **opts)

    def panel_solve(y: jax.Array):
        problem = LassoProblem(filt=filt, y=y, mu=mu, step=step)
        y2, tau, fwd, adj, soft, l1 = _lasso_setup(problem, backend, opts)
        stepf, init, final = machine(y2, tau, fwd, adj, soft, l1)

        def body(state, _):
            state, (trace, _stop) = stepf(state)
            return state, jnp.asarray(trace, jnp.float32)

        state, hist = jax.lax.scan(body, init(fwd(y2)), None, length=n_iters)
        a = final(state)
        return filt.adjoint(a, backend=backend, **opts), a, hist

    return panel_solve


def solve(problem, *, method: str | None = None, **kw) -> SolveResult:
    """Dispatch a problem to its solver by name.

    ``LassoProblem`` accepts ``method`` in {"ista", "fista"} (default
    "fista" — strictly fewer iterations for the same per-iteration
    communication); ``GramProblem`` accepts only "cg".
    """
    if isinstance(problem, LassoProblem):
        method = method or "fista"
        try:
            fn = {"ista": ista, "fista": fista}[method]
        except KeyError:
            raise ValueError(
                f"unknown lasso method {method!r}; use 'ista' or 'fista'"
            ) from None
        return fn(problem, **kw)
    if isinstance(problem, GramProblem):
        if method not in (None, "cg"):
            raise ValueError(f"GramProblem solves via 'cg', got {method!r}")
        return conjugate_gradient(problem, **kw)
    raise TypeError(f"unknown problem type {type(problem).__name__}")
