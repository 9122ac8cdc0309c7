"""Incremental Laplacian updates and churn-time filter corrections.

The streaming stack (repro.stream, repro.serve) was built for signals that
change per frame over a *frozen* graph. Real sensor fleets have nodes
joining, dying, and moving (ROADMAP item 5). This module makes topology
churn first-class:

* ``GraphDelta`` — a canonical batch of edge reweights (add = from 0,
  remove = to 0) plus vertex join/leave constructors under the slot-pool
  model: a vertex never disappears from the matrix, it becomes an isolated
  slot, so every array shape (and therefore every compiled program) is
  preserved across arbitrary churn.
* ``apply_graph_delta`` / ``apply_delta_inplace`` — functional and in-place
  (O(|delta|) for the Laplacian) applications of a delta.
* ``LmaxTracker`` — a cheaply re-certified upper bound on ``lambda_max``:
  rank-one degree bookkeeping keeps an Anderson--Morley-style bound valid
  in O(deg) per changed edge; only when the running bound degrades past
  the filter's domain does it fall back to an exact AM recompute and then
  a power iteration warm-started from the previous eigvector
  (``lmax_power_iteration(v0=...)``). Recomputing ``lmax`` from scratch
  per frame would change the polynomial — and retrace every program.
* The churn-correction kernels. With the Krylov stack
  ``t_k = Tbar_k(L) f`` retained from the previous frame
  (``cheb_apply_krylov``), the difference stack
  ``D_k := Tbar_k(L') f - Tbar_k(L) f`` for ``L' = L + dL`` obeys

      D_0 = 0,   D_1 = dL f / alpha,
      D_k = (2/alpha) (L' - alpha I) D_{k-1} - D_{k-2}
            + (2/alpha) dL t_{k-1},            k >= 2,

  which is exactly the shifted recurrence driven by ``dL t_{k-1}``.
  Since ``dL`` is supported on the changed-edge endpoints T, induction
  gives ``supp(D_k) ⊆ N_{k-1}(T)``; the whole degree-M correction is
  therefore computable *exactly* on the induced submatrix over
  ``N_M(T)`` — the same Chebyshev-locality argument as signal-delta
  filtering (DESIGN.md Secs. 8, 10) — and zero-padding to a power-of-two
  bucket is a fixed point of the recurrence, so compiled programs are
  reused across frames.
"""

from __future__ import annotations

import dataclasses
from typing import Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import chebyshev
from repro.core.graph import SensorGraph, lmax_power_iteration

__all__ = [
    "GraphDelta",
    "apply_graph_delta",
    "apply_delta_inplace",
    "LmaxTracker",
    "churn_correction",
    "restricted_cheb_apply_krylov",
    "dense_cheb_apply_krylov",
    "kernel_trace_counts",
]


@dataclasses.dataclass(frozen=True)
class GraphDelta:
    """A batch of topology changes between two consecutive frames.

    Attributes:
      edges: ``(u, v, new_weight)`` triples. ``new_weight`` is the
        *target* weight (not an increment): 0 removes the edge, a fresh
        pair adds one. Canonicalized on construction — ``u < v``,
        self-loops dropped, duplicate pairs last-wins.
      coords: optional (N, d) updated vertex coordinates (mobile fleets);
        carried through for plan-repair consumers that track geometry.
    """

    edges: tuple[tuple[int, int, float], ...]
    coords: np.ndarray | None = None

    def __post_init__(self):
        canon: dict[tuple[int, int], float] = {}
        for u, v, w in self.edges:
            u, v = int(u), int(v)
            if u == v:
                continue
            if u > v:
                u, v = v, u
            canon[(u, v)] = float(w)
        object.__setattr__(
            self, "edges", tuple((u, v, w) for (u, v), w in sorted(canon.items()))
        )

    def __len__(self) -> int:
        return len(self.edges)

    @property
    def touched(self) -> np.ndarray:
        """Sorted unique endpoints of every delta edge (the set T)."""
        if not self.edges:
            return np.zeros(0, dtype=np.int64)
        return np.unique(
            np.asarray([(u, v) for u, v, _ in self.edges], dtype=np.int64)
        )

    @classmethod
    def vertex_leave(cls, adjacency, vertex: int) -> "GraphDelta":
        """Vertex departure under the slot-pool model: zero every incident
        edge, leaving an isolated slot (shapes unchanged)."""
        a = np.asarray(adjacency)
        nbrs = np.nonzero(a[vertex])[0]
        return cls(tuple((int(vertex), int(n), 0.0) for n in nbrs))

    @classmethod
    def vertex_join(
        cls,
        vertex: int,
        neighbors: Sequence[int],
        weights: Sequence[float] | float = 1.0,
    ) -> "GraphDelta":
        """Vertex arrival: an isolated slot gains edges to ``neighbors``."""
        neighbors = [int(n) for n in neighbors]
        if np.ndim(weights) == 0:
            weights = [float(weights)] * len(neighbors)
        return cls(
            tuple((int(vertex), n, float(w)) for n, w in zip(neighbors, weights))
        )


def apply_graph_delta(graph: SensorGraph, delta: GraphDelta) -> SensorGraph:
    """Functionally apply a delta, returning a new ``SensorGraph``.

    The from-scratch reference for the incremental paths: parity tests
    rebuild plans/filters from ``apply_graph_delta``'s output and compare
    against the patched state.
    """
    a = np.array(graph.adjacency)
    for u, v, w in delta.edges:
        a[u, v] = a[v, u] = w
    coords = graph.coords
    if delta.coords is not None:
        coords = jnp.asarray(np.asarray(delta.coords), a.dtype)
    return SensorGraph(jnp.asarray(a), coords)


def apply_delta_inplace(
    adj: np.ndarray,
    lap: np.ndarray | None,
    delta: GraphDelta,
) -> tuple[np.ndarray, list[tuple[int, int, float]]]:
    """Mutate host adjacency (and Laplacian) in place; O(|delta|) work.

    Returns ``(touched, changed)`` where ``changed`` is the list of
    ``(u, v, dw)`` with ``dw = new - old`` for edges whose weight actually
    moved (no-op entries are dropped — their endpoints do not enter T),
    and ``touched`` are the sorted unique endpoints of ``changed``.
    """
    changed: list[tuple[int, int, float]] = []
    for u, v, w in delta.edges:
        dw = float(w) - float(adj[u, v])
        if dw == 0.0:
            continue
        adj[u, v] = adj[v, u] = w
        if lap is not None:
            lap[u, v] -= dw
            lap[v, u] -= dw
            lap[u, u] += dw
            lap[v, v] += dw
        changed.append((u, v, dw))
    if not changed:
        return np.zeros(0, dtype=np.int64), changed
    touched = np.unique(np.asarray([(u, v) for u, v, _ in changed], dtype=np.int64))
    return touched, changed


def _exact_am_bound(adj: np.ndarray, deg: np.ndarray) -> float:
    """Anderson--Morley: lambda_max <= max over edges of deg(u) + deg(v)."""
    pair = deg[:, None] + deg[None, :]
    masked = np.where(np.asarray(adj) > 0, pair, 0.0)
    return float(masked.max()) if masked.size else 0.0


class LmaxTracker:
    """Incrementally certified upper bound on ``lambda_max(L)``.

    Invariant: ``self.bound >= lambda_max`` of the current adjacency at
    all times (while ``method != "power"``, it even dominates the exact
    AM bound). The O(deg)-per-edge update reasons as follows: degrees
    change only at the endpoints of changed edges (the touched set T), so
    any edge with both endpoints outside T keeps its pair-sum — already
    covered by the previous bound. Taking the max of the previous bound
    and the fresh pair-sums of every edge incident to T re-covers the
    rest, hence the result dominates the new AM bound by induction. The
    price of cheapness is monotonicity: the running bound never decreases
    (edge removals loosen it), which is why ``recertify`` exists.
    """

    def __init__(self, adjacency: np.ndarray):
        a = np.asarray(adjacency)
        self.deg = a.sum(axis=1, dtype=np.float64)
        self.bound = _exact_am_bound(a, self.deg)
        self.method = "exact-am"
        self.recertifications = 0
        self._v: np.ndarray | None = None  # warm-start iterate across calls

    def update(self, adj: np.ndarray, changed: Iterable[tuple[int, int, float]]) -> float:
        """Fold a batch of edge changes into the certificate (cheap path)."""
        changed = list(changed)
        touched = set()
        for u, v, dw in changed:
            self.deg[u] += dw
            self.deg[v] += dw
            touched.add(u)
            touched.add(v)
        cand = 0.0
        for u in touched:
            nbrs = np.nonzero(np.asarray(adj[u]) > 0)[0]
            if nbrs.size:
                cand = max(cand, float((self.deg[u] + self.deg[nbrs]).max()))
        self.bound = max(self.bound, cand)
        self.method = "incremental-am"
        return self.bound

    def recertify(self, adj: np.ndarray) -> float:
        """Exact Anderson--Morley recompute — drops accumulated slack."""
        a = np.asarray(adj)
        self.deg = a.sum(axis=1, dtype=np.float64)
        self.bound = _exact_am_bound(a, self.deg)
        self.method = "exact-am"
        self.recertifications += 1
        return self.bound

    def power_estimate(self, lap: np.ndarray, *, iters: int = 50) -> float:
        """Tighten past AM with power iteration, warm-started from the
        previous topology's iterate (a small delta barely rotates the top
        eigvector, so few iterations suffice)."""
        est, v = lmax_power_iteration(
            jnp.asarray(lap), iters, v0=self._v, return_vector=True
        )
        self._v = np.asarray(v)
        est = float(est)
        if est < self.bound:
            self.bound = est
            self.method = "power"
        return self.bound


# ---------------------------------------------------------------------------
# Churn kernels. Module-level jits so the compile cache is keyed purely by
# bucket shapes: any frame whose reach pads to an already-seen power-of-two
# bucket reuses the compiled program. ``repro.obs`` counts each jaxpr trace
# JAX reports, by function name — what the steady-state-zero-recompiles pin
# measures.
# ---------------------------------------------------------------------------

_CHURN_KERNELS = (
    "churn_correction", "restricted_cheb_apply_krylov", "dense_cheb_apply_krylov")


def kernel_trace_counts() -> dict[str, int]:
    """Snapshot of per-kernel trace counts (compilations) so far."""
    traces = obs.compile_counts("trace")
    return {k: traces[k] for k in _CHURN_KERNELS if traces.get(k)}


@jax.jit
def churn_correction(lap_new_sub, dlap_sub, tk_sub, coeffs, lmax):
    """Exact filter-output correction after a Laplacian delta.

    Evaluates the difference recurrence (module docstring) on the induced
    submatrix over ``N_M(T)``, zero-padded to a bucket of size b.

    Args:
      lap_new_sub: (b, b) induced NEW Laplacian ``L'[R, R]``.
      dlap_sub: (b, b) induced delta ``dL[R, R]`` (entries only in
        T x T and diag(T), all inside R).
      tk_sub: (M+1, b, F) previous Krylov stack restricted to R.
      coeffs: (eta, M+1) Chebyshev coefficients.
      lmax: spectrum bound the coefficients were expanded on.

    Returns:
      ``(corr, d_stack)``: (eta, b, F) output correction and the
      (M+1, b, F) difference stack (add it to the stored Krylov stack to
      re-anchor it on ``L'``).
    """
    coeffs = jnp.asarray(coeffs, tk_sub.dtype)
    alpha = jnp.asarray(lmax, tk_sub.dtype) / 2.0
    d0 = jnp.zeros_like(tk_sub[0])
    d1 = (dlap_sub @ tk_sub[0]) / alpha
    # D_0 = 0, so the c_0/2 reconstruction term never contributes.
    acc = chebyshev._outer(coeffs[:, 1], d1)

    if coeffs.shape[1] <= 2:
        return acc, jnp.stack([d0, d1])

    def step(carry, xs):
        d_prev1, d_prev2, acc = carry
        c_k, t_prev = xs
        d_k = (
            (2.0 / alpha) * (lap_new_sub @ d_prev1 - alpha * d_prev1)
            - d_prev2
            + (2.0 / alpha) * (dlap_sub @ t_prev)
        )
        acc = acc + chebyshev._outer(c_k, d_k)
        return (d_k, d_prev1, acc), d_k

    (_, _, acc), ds = jax.lax.scan(
        step,
        (d1, d0, acc),
        (jnp.swapaxes(coeffs[:, 2:], 0, 1), tk_sub[1:-1]),
    )
    return acc, jnp.concatenate([jnp.stack([d0, d1]), ds], axis=0)


@jax.jit
def restricted_cheb_apply_krylov(lap_sub, d_sub, coeffs, lmax):
    """Signal-delta filtering on an induced submatrix, keeping the Krylov
    difference stack so the stored ``t_k`` can be updated too."""
    return chebyshev.cheb_apply_krylov(lambda v: lap_sub @ v, d_sub, coeffs, lmax)


@jax.jit
def dense_cheb_apply_krylov(lap, f, coeffs, lmax):
    """Full dense refilter that captures the Krylov stack — the churn
    path's activation / fallback frame."""
    return chebyshev.cheb_apply_krylov(lambda v: lap @ v, f, coeffs, lmax)
