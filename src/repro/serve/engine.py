"""Batched serving engines.

Two workloads share the static-batching pattern:

* ``ServeEngine`` — LM prefill/decode with per-request stop handling (the
  jit'd steps are the same functions the dry-run lowers for the decode
  cells).
* ``GraphFilterEngine`` — graph-signal filtering as a service: incoming
  (N,)-signal requests are packed into an (N, F) panel and answered by ONE
  ``GraphFilter.apply`` — the union recurrence is F-blind, so batching
  amortizes the whole Krylov sequence (and, on the ``bsr`` backend, feeds
  the Pallas step kernel MXU-shaped panels). This is the serving
  face of the paper's "one recurrence, eta outputs" economics.

  The same engine serves *iterative solves* (solve-as-a-service): requests
  queue on a second lane and one compiled FISTA/ISTA/CG run over the
  packed (N, F) panel answers F clients at once — the solver scan is as
  F-blind as a single apply, so an entire lasso denoising solve amortizes
  the same way (DESIGN.md Sec. 7.4). Configure with ``solver=`` (e.g.
  :func:`lasso_panel_solver`).

  A third lane serves *streams* (``submit_frame`` / ``flush_frames``):
  frames keyed by stream id are answered by per-stream
  :class:`repro.stream.StreamingFilter` state, so consecutive frames of a
  slowly varying signal pay delta-filtering work proportional to the
  boundary of change, not N — with per-frame latency and halo-words
  accounting on the engine (DESIGN.md Sec. 8).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.filters import GraphFilter
from repro.models import lm
from repro.models.config import ModelConfig, ParallelConfig
from repro.models.sharding import ShardingRules
from repro.solvers import LassoProblem, SolveResult, solve as solve_problem
from repro.stream import FrameResult, StreamingFilter

__all__ = [
    "make_decode_step",
    "make_prefill",
    "ServeEngine",
    "GraphFilterEngine",
    "lasso_panel_solver",
]


def make_decode_step(
    cfg: ModelConfig, par: ParallelConfig, rules: ShardingRules | None = None
) -> Callable:
    def decode_step(params, token, cache):
        return lm.decode_step(params, token, cache, cfg, par, rules)

    return decode_step


def make_prefill(
    cfg: ModelConfig,
    par: ParallelConfig,
    rules: ShardingRules | None = None,
    s_max: int | None = None,
) -> Callable:
    def prefill(params, tokens):
        return lm.prefill(params, tokens, cfg, par, rules, s_max=s_max)

    return prefill


_UNSET = object()


def _bind_solver_backend(solver, backend: str):
    """Bind a backend-less panel solver to the engine's backend.

    A :func:`lasso_panel_solver` built without an explicit ``backend=``
    declares ``backend=None`` ("inherit the engine's"), so the apply and
    solve lanes cannot silently disagree. Binding returns a *copy* via
    ``dataclasses.replace`` — mutating in place would leak this engine's
    backend into a solver object shared with another engine.

    Solvers with an explicit backend — or arbitrary callables that never
    declare one — pass through untouched. A non-dataclass solver that
    *does* declare ``backend=None`` is an error we refuse loudly: the old
    truthiness check (``getattr(..., "") is None``) skipped such solvers
    silently, and ``dataclasses.replace`` would raise a confusing
    ``TypeError`` deep inside ``__post_init__`` if it didn't.
    """
    if solver is None:
        return None
    declared = getattr(solver, "backend", _UNSET)
    if declared is not None:
        # Explicit backend, or no backend contract at all: use as-is.
        return solver
    if not dataclasses.is_dataclass(solver):
        raise TypeError(
            f"solver {type(solver).__name__!r} declares backend=None "
            "(meaning 'inherit the engine's backend') but is not a "
            "dataclass, so the engine cannot bind a copy with "
            "dataclasses.replace(). Construct it with an explicit "
            "backend= instead."
        )
    return dataclasses.replace(solver, backend=backend)


@dataclasses.dataclass
class ServeEngine:
    """Static-slot batched generation."""

    cfg: ModelConfig
    par: ParallelConfig
    params: Any
    s_max: int = 128
    temperature: float = 0.0
    rules: ShardingRules | None = None

    def __post_init__(self):
        self._decode = jax.jit(make_decode_step(self.cfg, self.par, self.rules))
        self._prefill = jax.jit(make_prefill(self.cfg, self.par, self.rules, s_max=self.s_max))

    def generate(
        self, prompts: np.ndarray, max_new_tokens: int, eos_id: int | None = None, seed: int = 0
    ) -> np.ndarray:
        """prompts: (B, S0) int32 -> (B, max_new_tokens) generated ids."""
        b = prompts.shape[0]
        logits, cache = self._prefill(self.params, jnp.asarray(prompts))
        key = jax.random.PRNGKey(seed)
        out = np.zeros((b, max_new_tokens), np.int32)
        done = np.zeros((b,), bool)
        token = self._sample(logits[:, -1], key)
        for t in range(max_new_tokens):
            out[:, t] = np.where(done, eos_id or 0, np.asarray(token[:, 0]))
            if eos_id is not None:
                done |= out[:, t] == eos_id
                if done.all():
                    break
            logits, cache = self._decode(self.params, token, cache)
            key = jax.random.fold_in(key, t)
            token = self._sample(logits[:, 0], key)
        return out

    def _sample(self, logits, key):
        if self.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        sampled = jax.random.categorical(key, logits / self.temperature, axis=-1)
        return sampled.astype(jnp.int32)[:, None]


@dataclasses.dataclass
class GraphFilterEngine:
    """Micro-batching front end for a :class:`GraphFilter`.

    Requests (one (N,) signal each) accumulate until ``panel_width`` are
    pending, then one backend apply answers the whole panel. A fixed panel
    width keeps the jit cache at a single entry (the partial last panel is
    zero-padded), which is also what the Pallas step kernel wants: a
    stable MXU-aligned F dimension.

    Parameters
    ----------
    filt : GraphFilter
        The filter to serve (graph already bound for graph-bound backends).
    backend : str
        ``GraphFilter`` backend to answer panels with.
    panel_width : int
        F dimension of the served panel; requests per apply.
    opts : dict
        Extra backend options forwarded to every apply.
    stream_opts : dict
        Keyword options for the per-stream
        :class:`repro.stream.StreamingFilter` lanes (``max_delta_frac``,
        ``refresh_every``, ``n_parts``, ...).
    """

    filt: GraphFilter
    backend: str = "bsr"
    panel_width: int = 8
    opts: dict = dataclasses.field(default_factory=dict)
    solver: Callable[[jax.Array], SolveResult] | None = None
    stream_opts: dict = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        self._pending: list[np.ndarray] = []
        self._pending_solves: list[np.ndarray] = []
        self._pending_frames: list[tuple[Any, np.ndarray]] = []
        self._streams: dict[Any, StreamingFilter] = {}
        self.served = 0
        self.applies = 0
        self.solved = 0
        self.solves = 0
        self.frames_served = 0
        self.stream_words = 0
        self.stream_latency_s = 0.0
        self.solver = _bind_solver_backend(self.solver, self.backend)

    def submit(self, signal) -> list[np.ndarray] | None:
        """Queue one (N,) signal; returns the panel's (eta, N) results —
        one array per queued request, submission order — when it fills."""
        self._pending.append(np.asarray(signal))
        if len(self._pending) >= self.panel_width:
            return self.flush()
        return None

    def flush(self) -> list[np.ndarray] | None:
        """Answer all pending requests now (pads a partial panel)."""
        if not self._pending:
            return None
        panel, k = self._pack(self._pending)
        out = self.filt.apply(jnp.asarray(panel), backend=self.backend, **self.opts)
        out = np.asarray(out)  # (eta, N, panel_width)
        self._pending.clear()
        self.served += k
        self.applies += 1
        return [out[:, :, i] for i in range(k)]

    # -- solve-as-a-service lane -----------------------------------------

    def submit_solve(self, signal) -> list[SolveResult] | None:
        """Queue one (N,) signal for the iterative-solve lane; returns the
        per-request :class:`SolveResult` list (submission order) when the
        panel fills."""
        if self.solver is None:
            raise ValueError("engine has no solver=; build one with lasso_panel_solver()")
        self._pending_solves.append(np.asarray(signal))
        if len(self._pending_solves) >= self.panel_width:
            return self.flush_solves()
        return None

    def flush_solves(self) -> list[SolveResult] | None:
        """Solve all pending requests now (pads a partial panel).

        The F queued signals are packed into one (N, F) panel and answered
        by a SINGLE solver run — on a traceable backend that is one
        compiled scan/while_loop whose every filter call carries the whole
        panel. Each caller receives the shared iteration/communication
        metadata with its own solution column.
        """
        if not self._pending_solves:
            # empty lane drains harmlessly, like flush() — even with no
            # solver configured
            return None
        if self.solver is None:
            raise ValueError("engine has no solver=; build one with lasso_panel_solver()")
        panel, k = self._pack(self._pending_solves)
        res = self.solver(jnp.asarray(panel))
        x = np.asarray(res.x)  # (N, panel_width)
        aux = None if res.aux is None else np.asarray(res.aux)
        self._pending_solves.clear()
        self.solved += k
        self.solves += 1
        return [
            dataclasses.replace(res, x=x[:, i], aux=None if aux is None else aux[..., i])
            for i in range(k)
        ]

    # -- streaming lane ---------------------------------------------------

    def submit_frame(self, stream_id, frame) -> list[FrameResult] | None:
        """Queue one (N,) frame on ``stream_id``'s streaming lane.

        Frames of the same stream are answered in submission order by a
        per-stream :class:`repro.stream.StreamingFilter` (delta filtering
        with cached state), so a slowly varying stream pays boundary-of-
        change work per frame instead of a full refilter. Auto-flushes
        when ``panel_width`` frames are pending; returns the flushed
        :class:`FrameResult` list (submission order) or None.
        """
        self._pending_frames.append((stream_id, np.asarray(frame)))
        if len(self._pending_frames) >= self.panel_width:
            return self.flush_frames()
        return None

    def flush_frames(self) -> list[FrameResult] | None:
        """Answer all pending frames now, in submission order.

        Per-frame latency and halo-words accounting accumulate on the
        engine (``frames_served``, ``stream_words``,
        ``stream_latency_s``) — the serving lane's observability hook.
        """
        if not self._pending_frames:
            return None
        results: list[FrameResult] = []
        for stream_id, frame in self._pending_frames:
            lane = self._streams.get(stream_id)
            if lane is None:
                lane = StreamingFilter(
                    self.filt,
                    backend=self.backend,
                    opts=self.opts,
                    **self.stream_opts,
                )
                self._streams[stream_id] = lane
            res = lane.push(frame)
            results.append(res)
            self.frames_served += 1
            self.stream_words += res.words
            self.stream_latency_s += res.latency_s
        self._pending_frames.clear()
        return results

    def _pack(self, pending: list[np.ndarray]) -> tuple[np.ndarray, int]:
        """Stack pending (N,) requests into a fixed-width (N, F) panel."""
        k = len(pending)
        panel = np.stack(pending, axis=1)  # (N, k)
        if panel.dtype == np.float64:  # host inputs default to f64
            panel = panel.astype(np.float32)
        if k < self.panel_width:
            panel = np.pad(panel, ((0, 0), (0, self.panel_width - k)))
        return panel, k


@dataclasses.dataclass
class _LassoPanelSolver:
    """Callable ``panel -> SolveResult`` for the engine's solve lane.

    ``backend=None`` means "not yet bound": :class:`GraphFilterEngine`
    fills it with its own backend at construction so the apply and solve
    lanes agree; standalone use falls back to ``"bsr"``.
    """

    filt: GraphFilter
    method: str
    mu: float | jax.Array
    step: float | None
    n_iters: int
    tol: float | None
    backend: str | None
    opts: dict

    def __call__(self, panel: jax.Array) -> SolveResult:
        problem = LassoProblem(filt=self.filt, y=panel, mu=self.mu, step=self.step)
        return solve_problem(
            problem,
            method=self.method,
            n_iters=self.n_iters,
            tol=self.tol,
            backend=self.backend or "bsr",
            **self.opts,
        )


def lasso_panel_solver(
    filt: GraphFilter,
    *,
    method: str = "fista",
    mu: float | jax.Array = 1.0,
    step: float | None = None,
    n_iters: int = 40,
    tol: float | None = None,
    backend: str | None = None,
    **opts,
) -> Callable[[jax.Array], SolveResult]:
    """Build a panel solver for :class:`GraphFilterEngine`'s solve lane.

    Returns ``panel -> SolveResult`` running SGWT-lasso denoising
    (:class:`repro.solvers.LassoProblem`) over the whole (N, F) panel with
    one ``method`` solve. A fixed panel width upstream keeps every run on
    identical shapes, so the compiled scan is reused across panels.
    Leave ``backend=None`` to inherit the owning engine's backend (set it
    explicitly only to make the lanes deliberately diverge).
    """
    return _LassoPanelSolver(
        filt=filt,
        method=method,
        mu=mu,
        step=step,
        n_iters=n_iters,
        tol=tol,
        backend=backend,
        opts=opts,
    )
