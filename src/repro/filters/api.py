"""``GraphFilter`` — the one entry point for Chebyshev-approximated unions
of graph Fourier multipliers (paper eqs. 8-11), backend-dispatched.

The paper's central object is a *union* of multipliers applied through one
shared Chebyshev recurrence. This module gives that object a single
surface::

    filt = GraphFilter.from_multipliers(bank, order=20, graph=g)
    out  = filt.apply(f, backend="bsr")      # (eta,) + f.shape
    back = filt.adjoint(out)                 # f.shape
    gram = filt.gram(f)                      # Phi~* Phi~ f, one 2M filter

Beyond the paper, a filter may be built over an ordered tuple of
*commuting shift operators* (arXiv:2003.11152 joint polynomials — e.g. a
time-vertex product of the sensor Laplacian and a temporal Laplacian)::

    filt = GraphFilter.from_shifts([g_sensor, g_time], joint_coeffs)
    out  = filt.apply(f, backend="halo")     # per-shift halo plans

Single-shift filters are the R = 1 special case of the same machinery.
Backends are looked up in ``repro.filters.registry`` and declare what they
support through a frozen ``BackendCapabilities`` record (``traceable``,
``sparse_input``, ``multi_shift``); see DESIGN.md Sec. 6 / 11 for the
dispatch design and the backend support matrix in README.md.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import chebyshev
from repro.core.graph import SensorGraph
from repro.filters import registry

__all__ = ["GraphFilter", "bucket_size", "shift_matvec_counts"]

Multiplier = Callable[[np.ndarray], np.ndarray]

_BUCKET_FLOOR = 32


def bucket_size(n: int, cap: int | None = None, *, floor: int = _BUCKET_FLOOR) -> int:
    """Round ``n`` up to a power-of-two bucket (optionally capped).

    The shape-stability primitive shared by the streaming delta path
    (submatrix sizes), the serving engine (panel widths), and
    :meth:`GraphFilter.apply_panel`: quantizing a wobbling dimension to
    power-of-two buckets means a handful of compiled programs serve every
    workload instead of one trace per novel shape.

    The bucket set is ``{floor * 2**k} ∪ {cap}``: the power-of-two ladder
    starts at ``floor``, and ``cap`` — when given — is the one permitted
    non-ladder value (the caller's hard "full size", e.g. the vertex count
    N for submatrices or the scheduler's ``max_panel``). Pinned behavior:

    * ``n > cap`` returns ``cap`` exactly — the caller's clamp always
      wins, even though the bucket no longer covers ``n`` (stream and
      serve both detect ``bucket >= cap`` and fall back to the full-size
      path).
    * a ``cap`` that is not a power of two is returned verbatim whenever
      the ladder crosses it — never rounded, since the cap *is* a real
      compiled shape (the full problem size).
    * ``cap < floor`` returns ``cap`` (the clamp also beats the floor).

    Parameters
    ----------
    n : int
        The true size to cover (``n <= bucket_size(n, ...)`` unless the
        cap clamps). Must be >= 0.
    cap : int, optional
        Upper clamp; must be >= 1 when given.
    floor : int
        Smallest ladder bucket; must be >= 1. Coarser floors mean fewer
        programs.
    """
    if n < 0:
        raise ValueError(f"bucket_size needs n >= 0, got {n}")
    if floor < 1:
        raise ValueError(f"bucket_size needs floor >= 1, got {floor}")
    if cap is not None and cap < 1:
        raise ValueError(f"bucket_size needs cap >= 1, got {cap}")
    b = floor
    while b < n:
        b *= 2
    return b if cap is None else min(b, cap)


def shift_matvec_counts(orders: Sequence[int]) -> tuple[int, ...]:
    """Per-shift matvec counts of one joint apply (DESIGN.md Sec. 11.2).

    The joint recurrence restarts shift r's Krylov sequence once per
    combination of outer Krylov vectors, so shift r performs
    ``M_r * prod_{s<r} (M_s + 1)`` matvecs. For one shift this is the
    familiar M; the per-shift words model multiplies each count by that
    shift's own ``halo_words``.
    """
    counts: list[int] = []
    prefix = 1
    for m in orders:
        counts.append(int(m) * prefix)
        prefix *= int(m) + 1
    return tuple(counts)


@dataclasses.dataclass(frozen=True, eq=False)
class GraphFilter:
    """A Chebyshev-approximated union of graph Fourier multipliers.

    Identity semantics (``eq=False``): filters compare and hash by object
    identity — array-valued fields make structural equality ill-defined,
    and identity hashing lets a filter serve as a dict key or jit static
    argument.

    Carries the *spectral* description only — the coefficient tensor, the
    spectrum bound(s), and the shift structure. Graph-operator operands
    (dense Laplacians, Block-CSR tiles, partition plans) are built lazily
    per backend and cached.

    Parameters
    ----------
    coeffs : numpy.ndarray
        (eta, M+1) Chebyshev coefficients — paper eq. (8) convention (the
        k = 0 term enters with a 1/2 factor at evaluation time). For a
        multi-shift filter, the joint (eta, M_1+1, ..., M_R+1) tensor with
        the half convention applied per axis.
    lmax : float
        Upper bound on the (first) shift's spectrum the polynomials were
        shifted to (paper Sec. IV-A: need not be tight).
    gram_coeffs : numpy.ndarray
        (2M+1,) coefficients of ``Phi~* Phi~`` as a single filter (paper
        Sec. IV-C product identity); the (2M_1+1, ..., 2M_R+1) joint
        tensor for multi-shift filters.
    graph : SensorGraph, optional
        The (first-shift) graph this filter is bound to. Required by every
        backend except ``"matvec"``; bind one with :meth:`bind`.
    multipliers : tuple of callables, optional
        The original multiplier bank ``g_j: [0, lmax] -> R`` (kept for
        re-expansion and diagnostics; single-shift only).
    shifts : tuple of SensorGraph, optional
        The full ordered shift tuple for a multi-shift filter
        (``shifts[0] is graph``); None on single-shift filters.
    lmaxes : tuple of float, optional
        Per-shift spectrum bounds (``lmaxes[0] == lmax``); None on
        single-shift filters.

    Examples
    --------
    >>> g = graph.connected_sensor_graph(jax.random.PRNGKey(0), n=500)
    >>> filt = GraphFilter.from_multipliers(
    ...     [multipliers.tikhonov(1.0, 1)], order=20, graph=g)
    >>> denoised = filt.apply(y, backend="dense")[0]
    """

    coeffs: np.ndarray
    lmax: float
    gram_coeffs: np.ndarray
    graph: SensorGraph | None = None
    multipliers: tuple[Multiplier, ...] | None = None
    shifts: tuple[SensorGraph, ...] | None = None
    lmaxes: tuple[float, ...] | None = None
    _states: dict = dataclasses.field(default_factory=dict, repr=False, compare=False)

    # -- constructors ----------------------------------------------------

    @classmethod
    def from_multipliers(
        cls,
        multipliers: Sequence[Multiplier],
        order: int,
        *,
        graph: SensorGraph | None = None,
        lmax: float | None = None,
        quad_points: int | None = None,
    ) -> "GraphFilter":
        """Expand a multiplier bank to Chebyshev coefficients (eq. 8).

        The single-shift convenience constructor (R = 1).

        Parameters
        ----------
        multipliers : sequence of callables
            ``eta`` numpy-vectorized kernels ``g_j: [0, lmax] -> R``.
        order : int
            Truncation order M (paper: M ~ 20 suffices in practice).
        graph : SensorGraph, optional
            Graph to bind; when given and ``lmax`` is None, the
            Anderson--Morley bound ``graph.lmax_bound()`` is used.
        lmax : float, optional
            Explicit spectrum bound (required if ``graph`` is None).
        quad_points : int, optional
            Chebyshev--Gauss quadrature nodes for eq. (8).

        Returns
        -------
        GraphFilter
        """
        if lmax is None:
            if graph is None:
                raise ValueError("need either graph= or lmax=")
            lmax = float(graph.lmax_bound())
        c = chebyshev.cheb_coefficients(multipliers, order, lmax, quad_points)
        return cls(
            coeffs=c,
            lmax=float(lmax),
            gram_coeffs=chebyshev.gram_coefficients(c),
            graph=graph,
            multipliers=tuple(multipliers),
        )

    @classmethod
    def from_coefficients(
        cls,
        coeffs: np.ndarray,
        lmax: float,
        *,
        graph: SensorGraph | None = None,
    ) -> "GraphFilter":
        """Wrap precomputed (eta, M+1) coefficients in a filter."""
        c = np.atleast_2d(np.asarray(coeffs, dtype=np.float64))
        return cls(
            coeffs=c,
            lmax=float(lmax),
            gram_coeffs=chebyshev.gram_coefficients(c),
            graph=graph,
        )

    @classmethod
    def from_shifts(
        cls,
        shifts: Sequence[SensorGraph],
        coeffs: np.ndarray,
        *,
        lmaxes: Sequence[float] | None = None,
    ) -> "GraphFilter":
        """Build a joint polynomial filter over an ordered shift tuple.

        Expresses product/joint polynomials of several *commuting* shift
        operators (arXiv:2003.11152) — the canonical instance being the
        time-vertex Cartesian product, where shift 1 is the sensor
        Laplacian acting along the vertex axis and shift 2 a temporal
        Laplacian along the time axis (``L_G (x) I`` and ``I (x) L_T``
        commute by construction). Every shift graph must have the same
        vertex count — the product graph's, with each adjacency encoding
        that shift's edges only, so each shift carries its own halo
        exchange plan on distributed backends.

        Parameters
        ----------
        shifts : sequence of SensorGraph
            R graphs over the same (product) vertex set; ``shifts[r]``'s
            Laplacian is the r-th shift operator.
        coeffs : numpy.ndarray
            Joint (eta, M_1+1, ..., M_R+1) coefficient tensor (an
            (M_1+1, ..., M_R+1) tensor is promoted to eta = 1). Build
            separable tensors with
            ``chebyshev.separable_joint_coefficients``.
        lmaxes : sequence of float, optional
            Per-shift spectrum bounds; defaults to each graph's
            Anderson--Morley ``lmax_bound()``.
        """
        shifts = tuple(shifts)
        if not shifts:
            raise ValueError("from_shifts needs at least one shift")
        n = shifts[0].n_vertices
        for r, g in enumerate(shifts):
            if g.n_vertices != n:
                raise ValueError(
                    f"shift {r} has {g.n_vertices} vertices, shift 0 has {n};"
                    " all shifts act on the same product vertex set"
                )
        c = np.asarray(coeffs, dtype=np.float64)
        if c.ndim == len(shifts):
            c = c[np.newaxis]
        if c.ndim != len(shifts) + 1:
            raise ValueError(
                f"joint coeffs for {len(shifts)} shifts must have ndim "
                f"{len(shifts) + 1} (eta leading), got shape {c.shape}"
            )
        if lmaxes is None:
            lmaxes = tuple(float(g.lmax_bound()) for g in shifts)
        else:
            lmaxes = tuple(float(v) for v in lmaxes)
            if len(lmaxes) != len(shifts):
                raise ValueError(f"{len(lmaxes)} lmaxes for {len(shifts)} shifts")
        return cls(
            coeffs=c,
            lmax=lmaxes[0],
            gram_coeffs=chebyshev.joint_gram_coefficients(c),
            graph=shifts[0],
            shifts=shifts,
            lmaxes=lmaxes,
        )

    def bind(self, graph: SensorGraph) -> "GraphFilter":
        """Return a copy bound to ``graph`` (backend states reset).

        Single-shift only — rebind a multi-shift filter by rebuilding it
        with :meth:`from_shifts` (every shift graph changes together).
        """
        if self.n_shifts > 1:
            raise ValueError(
                "bind() is single-shift; rebuild multi-shift filters with "
                "GraphFilter.from_shifts"
            )
        return dataclasses.replace(self, graph=graph, _states={})

    # -- introspection ---------------------------------------------------

    @property
    def eta(self) -> int:
        """Number of multipliers in the union."""
        return self.coeffs.shape[0]

    @property
    def n_shifts(self) -> int:
        """Number of shift operators (1 for classic single-shift filters)."""
        return self.coeffs.ndim - 1

    @property
    def order(self) -> int:
        """Chebyshev truncation order M (single-shift filters only)."""
        if self.n_shifts > 1:
            raise ValueError(
                f"multi-shift filter has per-shift orders {self.orders}; "
                "use .orders"
            )
        return self.coeffs.shape[1] - 1

    @property
    def orders(self) -> tuple[int, ...]:
        """Per-shift truncation orders (M_1, ..., M_R)."""
        return tuple(m - 1 for m in self.coeffs.shape[1:])

    @property
    def shift_graphs(self) -> tuple[SensorGraph | None, ...]:
        """The ordered shift tuple ((graph,) for single-shift filters)."""
        return self.shifts if self.shifts is not None else (self.graph,)

    @property
    def shift_lmaxes(self) -> tuple[float, ...]:
        """Per-shift spectrum bounds ((lmax,) for single-shift filters)."""
        return self.lmaxes if self.lmaxes is not None else (self.lmax,)

    def operator_norm_bound(self) -> float:
        """Upper bound on ``||Phi~||^2 = max_x sum_j p_j(x)^2`` over the
        shifted domain — e.g. to pick the ISTA step ``tau < 2/||W~||^2``.
        Multi-shift filters maximize over the tensor spectral grid."""
        if self.n_shifts == 1:
            x = np.linspace(0.0, self.lmax, 8192)
            vals = np.atleast_2d(chebyshev.cheb_eval(self.coeffs, x, self.lmax))
        else:
            pts = max(64, int(round(8192 ** (1.0 / self.n_shifts))))
            xs = [np.linspace(0.0, lm, pts) for lm in self.shift_lmaxes]
            vals = chebyshev.cheb_eval_joint(self.coeffs, xs, self.shift_lmaxes)
            vals = vals.reshape(self.eta, -1)
        return float(np.max(np.sum(vals**2, axis=0)))

    # -- backend dispatch ------------------------------------------------

    def _backend(self, name: str) -> registry.FilterBackend:
        """Resolve a backend and enforce this filter's capability needs."""
        be = registry.get_backend(name)
        if self.n_shifts > 1:
            registry.require_capability(be, "multi_shift")
        return be

    def _backend_state(self, be: registry.FilterBackend, opts: dict) -> Any:
        # Backends that share prepared operands (halo/allgather both use
        # the same partition plan) declare a common ``state_key``.
        key = (getattr(be, "state_key", be.name),) + tuple(
            sorted((k, v) for k, v in opts.items() if k in be.prepare_opts)
        )
        if key not in self._states:
            with obs.span("filter.prepare", backend=be.name):
                self._states[key] = be.prepare(self, **opts)
        return self._states[key]

    def prepare_backend(self, backend: str = "dense", **opts) -> Any:
        """Eagerly build (and cache) ``backend``'s prepared state, and
        return it (e.g. the Block-CSR operands of ``bsr``, the partition
        plan of ``halo``).

        Normally preparation happens lazily on the first apply; callers
        staging a trace (``jax.jit`` over a filter call) use this so the
        prepared operands are concrete before tracing begins.
        """
        be = self._backend(backend)
        return self._backend_state(be, opts)

    def apply(self, f: jax.Array, *, backend: str = "dense", **opts) -> jax.Array:
        """Apply the union ``Phi~ f`` through one shared recurrence.

        Parameters
        ----------
        f : jax.Array
            Input signal, shape (N,) or (N, F) for a batch of F signals.
        backend : str
            Registered backend name — one of
            ``repro.filters.available_backends()``; shipping backends are
            ``dense``, ``bsr``, ``halo``, ``allgather``, ``grid`` and the
            graph-free ``matvec``. Multi-shift filters require a backend
            declaring the ``multi_shift`` capability (dense/bsr/halo).
        **opts
            Backend options (e.g. ``block_size=`` / ``krylov_dtype=`` for
            ``bsr``, ``mesh=`` / ``axis=`` for distributed backends,
            ``overlap=`` for ``halo``, ``matvec=`` for ``matvec``).

        Returns
        -------
        jax.Array
            (eta,) + f.shape stacked outputs ``[Psi~_1 f, ..., Psi~_eta f]``.
        """
        be = self._backend(backend)
        return be.apply(self, self._backend_state(be, opts), f, **opts)

    def apply_panel(
        self,
        panel: jax.Array,
        *,
        backend: str = "dense",
        width: int | None = None,
        **opts,
    ) -> jax.Array:
        """Apply to an (N, F) panel zero-padded to a bucketed width.

        The shape-bucketed serving entry: the panel's F dimension is
        padded up to ``width`` (default: the next power-of-two bucket,
        floor 8) before the backend apply and sliced back afterwards, so
        callers with wobbling panel widths reuse a logarithmic number of
        compiled programs instead of retriggering a trace per novel F.
        Zero columns are exact pass-throughs — every shipped operation is
        linear in the signal — so padding changes no output column.

        Parameters
        ----------
        panel : jax.Array
            (N, F) batch of F signals.
        width : int, optional
            Explicit target width (must be >= F); default buckets F.

        Returns
        -------
        jax.Array
            (eta, N, F) — identical to ``apply(panel)``.
        """
        f = jnp.asarray(panel)
        if f.ndim != 2:
            raise ValueError(f"apply_panel wants an (N, F) panel, got {f.shape}")
        k = f.shape[1]
        b = bucket_size(k, floor=8) if width is None else int(width)
        if b < k:
            raise ValueError(f"width={b} narrower than the panel's F={k}")
        if b > k:
            f = jnp.pad(f, ((0, 0), (0, b - k)))
        out = self.apply(f, backend=backend, **opts)
        return out[:, :, :k]

    def panel_program(
        self, *, backend: str = "dense", coeffs=None, donate: bool = False,
        **opts
    ) -> Callable[[jax.Array], jax.Array]:
        """Build a reusable fixed-shape apply program for a panel lane.

        Returns ``panel (N, F) -> (eta, N, F)`` with the backend state
        prepared eagerly and — on backends declaring the ``traceable``
        capability — the whole apply wrapped in one ``jax.jit``, so a
        serving engine can key compiled programs by panel bucket and
        count recompiles exactly (one trace per program, on its first
        call). Non-traceable backends (halo/grid place arrays on their
        mesh with explicit ``device_put`` calls outside any trace) return
        a plain callable; their compilation reuse lives in their own
        prepared state.

        ``donate=True`` donates the panel input buffer to the program
        (``launch.donation`` discipline): the serving engine packs a fresh
        panel per batch and never touches it after the call, so XLA may
        reuse that allocation for the (eta, N, F) output — the panel lane
        stays allocation-stable at steady state. Callers that keep the
        panel alive must leave the default.
        """
        be = self._backend(backend)
        state = self._backend_state(be, opts)
        c = coeffs

        def panel_apply(panel: jax.Array) -> jax.Array:
            return be.apply(self, state, panel, coeffs=c, **opts)

        if be.capabilities.traceable:
            return jax.jit(panel_apply, donate_argnums=(0,) if donate else ())
        return panel_apply

    def apply_sparse(
        self,
        delta: jax.Array,
        support,
        *,
        backend: str = "dense",
        **opts,
    ) -> jax.Array:
        """Apply ``Phi~`` to a signal supported on a sparse vertex set.

        The streaming layer's delta path (DESIGN.md Sec. 8): when ``delta``
        is nonzero only on ``support``, the degree-M recurrence touches
        only the M-hop neighbourhood of that set, so backends declaring the
        ``sparse_input`` capability run it on the induced submatrix —
        cost (flops and halo words) scales with the neighbourhood size,
        not N. Backends without the capability — and multi-shift filters,
        whose reach spans several edge sets — fall back to a full
        ``apply`` (identical output, no savings).

        Parameters
        ----------
        delta : jax.Array
            (N,) or (N, F) signal, zero outside ``support``.
        support : array-like
            (N,) boolean mask (or index array) of the nonzero vertices.
        backend : str
            Registered backend name.

        Returns
        -------
        jax.Array
            (eta,) + delta.shape — equal to ``apply(delta)`` up to float
            tolerance, zero outside the M-hop reach of ``support``.
        """
        be = self._backend(backend)
        if not be.capabilities.sparse_input or self.n_shifts > 1:
            return self.apply(delta, backend=backend, **opts)
        state = self._backend_state(be, opts)
        return be.apply_sparse(self, state, delta, support, **opts)

    def adjoint(self, a: jax.Array, *, backend: str = "dense", **opts) -> jax.Array:
        """Apply the adjoint ``Phi~* a`` (paper eq. 13 / Sec. IV-B).

        Parameters
        ----------
        a : jax.Array
            (eta,) + signal.shape stacked coefficient signals.

        Returns
        -------
        jax.Array
            signal.shape adjoint output.
        """
        be = self._backend(backend)
        return be.adjoint(self, self._backend_state(be, opts), a, **opts)

    def apply_series(
        self,
        f: jax.Array,
        series: np.ndarray,
        *,
        backend: str = "dense",
        **opts,
    ) -> jax.Array:
        """Apply an arbitrary polynomial ``p(S_1..S_R) f`` in this
        filter's shifts, reusing the prepared backend state.

        ``series`` is one (M'+1,)-shaped coefficient vector — or a joint
        (M'_1+1, ..., M'_R+1) tensor for multi-shift filters — in the
        usual half-first-coefficient convention; its degree need not match
        the filter's. This is how ``gram`` runs the degree-2M product
        series and how the Chebyshev inverse preconditioner
        (``repro.solvers.cheb_inverse``) applies its fitted
        ``q(lambda) ~= 1/h(lambda)`` polynomial without building a second
        filter (same Laplacian operands, same plans, zero extra prepares).
        """
        c = np.asarray(series, dtype=np.float64)
        if c.ndim != self.n_shifts:
            raise ValueError(
                f"series for a {self.n_shifts}-shift filter must have ndim "
                f"{self.n_shifts}, got shape {c.shape}"
            )
        be = self._backend(backend)
        state = self._backend_state(be, opts)
        out = be.apply(self, state, f, coeffs=c[np.newaxis], **opts)
        return out[0]

    def gram(self, f: jax.Array, *, backend: str = "dense", **opts) -> jax.Array:
        """``Phi~* Phi~ f`` as a *single* degree-2M filter (Sec. IV-C).

        Costs 2M matvecs — half of composing ``adjoint(apply(f))``.
        """
        return self.apply_series(f, self.gram_coeffs, backend=backend, **opts)

    def messages_per_apply(
        self,
        order: int | None = None,
        *,
        orders: Sequence[int] | None = None,
        backend: str = "halo",
        **opts,
    ) -> int:
        """Scalar words exchanged between workers per ``Phi~ f``.

        The paper's radio model bounds one apply by ``2 M |E|`` length-1
        messages (each of the M recurrence steps sends every vertex value
        across every edge, both directions). Per backend:

        * ``dense`` / ``bsr`` / ``matvec`` — 0: single-device, the
          "communication" is HBM traffic, not network words.
        * ``halo`` — ``sum_r count_r * halo_words_r``: each shift r
          performs ``count_r = M_r * prod_{s<r}(M_s + 1)`` matvecs on its
          own exchange plan (for one shift: ``M * halo_words`` with
          ``halo_words <= 2|E|`` — a boundary vertex is sent once per
          neighbouring *partition*, not once per edge, so the mesh does
          no worse than the radio bound).
        * ``allgather`` — ``M * n_local * P * (P - 1)``: every device ships
          its whole slab to everyone each order (the §Perf "before").
        * ``grid`` — ``M * 2 * (P - 1) * side``: one boundary row up and
          down per order; the communication-avoiding schedule (depth d)
          moves the same words in M/d rounds.

        Parameters
        ----------
        order : int, optional
            Recurrence order M (single-shift filters only); defaults to
            this filter's order. Solvers pass e.g. ``2M`` for the gram
            series.
        orders : sequence of int, optional
            Per-shift orders (multi-shift); defaults to ``self.orders``.
            Mutually exclusive with ``order``.
        backend : str
            Backend whose communication model to evaluate.

        Returns
        -------
        int
            Scalar words per apply of one (N,) signal.
        """
        if order is not None and orders is not None:
            raise ValueError("pass order= or orders=, not both")
        if orders is None:
            if order is not None:
                if self.n_shifts > 1:
                    raise ValueError(
                        "multi-shift filter: pass per-shift orders= "
                        "instead of a scalar order="
                    )
                orders = (int(order),)
            else:
                orders = self.orders
        elif len(orders) != self.n_shifts:
            raise ValueError(f"{len(orders)} orders for {self.n_shifts} shifts")
        be = self._backend(backend)
        state = self._backend_state(be, opts)
        return be.messages_per_apply(self, state, shift_matvec_counts(orders))
