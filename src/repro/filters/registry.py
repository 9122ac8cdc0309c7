"""Backend registry for :class:`repro.filters.GraphFilter`.

One filtering primitive, many execution substrates (DESIGN.md Sec. 6): a
backend packages how ``Phi~ f`` / ``Phi~* a`` are evaluated — dense matmul,
the Pallas Block-CSR kernel, or a ``shard_map``-distributed matvec — behind
a small protocol, so new substrates (GPU sparse, multi-host) drop in by
registering one class and never touch callers.

The protocol mirrors the paper's separation of concerns: the *spectral* data
(coefficients, lmax, shift structure) lives on the filter; the
*graph-operator* data (dense Laplacian, Block-CSR tiles, partition plans) is
backend state built once by ``prepare`` and cached on the filter per backend.

What a backend can do is declared in one frozen
:class:`BackendCapabilities` record (the PR-9 capability protocol — it
replaces the earlier ad-hoc per-class ``traceable`` / ``sparse_input``
boolean attributes). Callers consult it through the thin accessors below or
enforce it with :func:`require_capability`, whose error names both the
backend and the missing capability.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Protocol, Sequence, runtime_checkable

import jax

__all__ = [
    "BackendCapabilities",
    "FilterBackend",
    "register_backend",
    "get_backend",
    "available_backends",
    "backend_capabilities",
    "backend_is_traceable",
    "backend_supports_sparse",
    "backend_supports_multi_shift",
    "require_capability",
]


@dataclasses.dataclass(frozen=True)
class BackendCapabilities:
    """What a registered backend can do, as one frozen record.

    Attributes
    ----------
    traceable : bool
        True iff ``apply``/``adjoint``/``gram`` stage pure jax ops end to
        end, so calls can live inside ``jax.lax.scan`` / ``while_loop``
        bodies (iterative solvers then compile their whole loop). Backends
        that move arrays between device sets with explicit ``device_put``
        calls (the mesh backends' scatter and gather) must declare False —
        callers fall back to a host loop.
    sparse_input : bool
        True iff the backend implements ``apply_sparse`` — applying the
        filter to a signal supported on a sparse vertex set by restricting
        the recurrence to its order-hop neighbourhood (the streaming
        layer's delta path, DESIGN.md Sec. 8). Without it,
        ``GraphFilter.apply_sparse`` falls back to a full ``apply``
        (correct, no savings).
    multi_shift : bool
        True iff the backend evaluates joint polynomials of several shift
        operators (``GraphFilter.from_shifts``, DESIGN.md Sec. 11) — one
        local recurrence per shift, each with its own exchange plan.
        Backends without it reject multi-shift filters loudly via
        :func:`require_capability` instead of silently using only the
        first shift.
    """

    traceable: bool = False
    sparse_input: bool = False
    multi_shift: bool = False


@runtime_checkable
class FilterBackend(Protocol):
    """Protocol every ``GraphFilter`` backend implements.

    Attributes
    ----------
    name : str
        Registry key, e.g. ``"dense"`` or ``"halo"``.
    prepare_opts : frozenset of str
        Names of keyword options that select *which* prepared state is used
        (they become part of the filter's state-cache key and must be
        hashable). All other options only affect the individual call.
    state_key : str, optional
        Cache key for prepared state; defaults to ``name``. Backends whose
        ``prepare`` builds identical operands (halo/allgather share one
        partition plan) declare a common value to share the state.
    capabilities : BackendCapabilities
        The backend's declared capability record (required — registration
        rejects classes without one).
    """

    name: str
    prepare_opts: frozenset[str]
    capabilities: BackendCapabilities

    def prepare(self, filt, **opts) -> Any:
        """Build backend state (operands, plans) for ``filt``; called once
        per (filter, prepare-opts) pair and cached."""
        ...

    def apply(self, filt, state, f, *, coeffs=None, **opts) -> jax.Array:
        """``Phi~ f`` -> (eta,) + f.shape (``coeffs`` overrides the
        filter's, used by ``gram`` and the polynomial preconditioners)."""
        ...

    def adjoint(self, filt, state, a, **opts) -> jax.Array:
        """``Phi~* a`` for ``a`` shaped (eta,) + signal.shape."""
        ...

    def messages_per_apply(
        self, filt, state, matvec_counts: Sequence[int]
    ) -> int:
        """Scalar words exchanged between workers per apply, given the
        per-shift matvec counts (0 when the backend is single-device);
        see DESIGN.md Sec. 6.2 / 11.2."""
        ...


_REGISTRY: dict[str, FilterBackend] = {}


def register_backend(cls):
    """Class decorator: instantiate and register a backend under its
    ``name``. Re-registering a name overwrites (supports reloads)."""
    backend = cls()
    if not isinstance(backend, FilterBackend):
        raise TypeError(f"{cls!r} does not implement FilterBackend")
    if not isinstance(
        getattr(backend, "capabilities", None), BackendCapabilities
    ):
        raise TypeError(
            f"{cls!r} must declare capabilities as a BackendCapabilities "
            "instance"
        )
    _REGISTRY[backend.name] = backend
    return cls


def get_backend(name: str) -> FilterBackend:
    """Look up a registered backend by name.

    Raises
    ------
    KeyError
        With the list of available backends, if ``name`` is unknown.
    """
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown filter backend {name!r}; available: {sorted(_REGISTRY)}"
        ) from None


def available_backends() -> tuple[str, ...]:
    """Names of all registered backends, sorted."""
    return tuple(sorted(_REGISTRY))


def backend_capabilities(name: str) -> BackendCapabilities:
    """The :class:`BackendCapabilities` record of backend ``name``."""
    return get_backend(name).capabilities


def backend_is_traceable(name: str) -> bool:
    """True iff backend ``name`` declares the ``traceable`` capability —
    i.e. its filter calls may be placed inside ``lax.scan``/``while_loop``
    bodies."""
    return backend_capabilities(name).traceable


def backend_supports_sparse(name: str) -> bool:
    """True iff backend ``name`` declares the ``sparse_input`` capability —
    i.e. it implements ``apply_sparse`` (restricted-support delta
    filtering)."""
    return backend_capabilities(name).sparse_input


def backend_supports_multi_shift(name: str) -> bool:
    """True iff backend ``name`` evaluates multi-shift joint filters
    (``GraphFilter.from_shifts``)."""
    return backend_capabilities(name).multi_shift


def require_capability(backend: FilterBackend | str, capability: str) -> None:
    """Raise unless ``backend`` declares ``capability``.

    The error names both, so a failed dispatch reads as a capability
    mismatch rather than a shape error deep inside the backend::

        backend 'allgather' does not support capability 'multi_shift';
        supported backends: ['bsr', 'dense', 'halo']
    """
    be = get_backend(backend) if isinstance(backend, str) else backend
    caps = be.capabilities
    if not hasattr(caps, capability):
        raise AttributeError(
            f"unknown capability {capability!r}; declared capabilities: "
            f"{[f.name for f in dataclasses.fields(caps)]}"
        )
    if not getattr(caps, capability):
        supported = sorted(
            n
            for n, b in _REGISTRY.items()
            if getattr(b.capabilities, capability, False)
        )
        raise ValueError(
            f"backend {be.name!r} does not support capability "
            f"{capability!r}; supported backends: {supported}"
        )
