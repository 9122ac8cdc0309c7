"""Graph construction and Laplacian utilities (paper Sec. II).

The paper models a sensor network as an undirected weighted graph
``G = {V, E, w}`` with the thresholded-Gaussian edge weighting of eq. (1):

    w(e_ij) = exp(-d(i,j)^2 / (2 sigma^2))  if d(i,j) <= kappa, else 0.

This module builds such graphs, their (non-normalized) Laplacians
``L = D - A``, and the Anderson--Morley upper bound on ``lambda_max``
used by the distributed algorithm (the bound "need not be tight", Sec. IV-A).

All dense outputs are plain ``jnp`` arrays so they compose with jit/vmap;
host-only utilities (connectivity check, partitioning) use numpy.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

__all__ = [
    "SensorGraph",
    "gaussian_kernel_weights",
    "random_sensor_graph",
    "grid_graph",
    "ring_graph",
    "torus_graph",
    "laplacian",
    "degree_vector",
    "lmax_upper_bound",
    "lmax_power_iteration",
    "is_connected",
    "khop_neighborhood",
    "spatial_partition_order",
    "degree_order",
]


@dataclasses.dataclass(frozen=True)
class SensorGraph:
    """A weighted undirected graph plus optional vertex coordinates.

    Attributes:
      adjacency: (N, N) symmetric non-negative weight matrix, zero diagonal.
      coords:    (N, d) vertex coordinates, or None for abstract graphs.
    """

    adjacency: jax.Array
    coords: jax.Array | None = None

    @property
    def n_vertices(self) -> int:
        return self.adjacency.shape[0]

    @property
    def n_edges(self) -> int:
        """|E| — number of undirected edges with non-zero weight."""
        return int(np.count_nonzero(np.asarray(self.adjacency)) // 2)

    def laplacian(self) -> jax.Array:
        with obs.span("graph.laplacian", n=self.n_vertices):
            return laplacian(self.adjacency)

    def lmax_bound(self) -> jax.Array:
        return lmax_upper_bound(self.adjacency)


def gaussian_kernel_weights(
    coords: jax.Array, sigma: float, kappa: float
) -> jax.Array:
    """Thresholded Gaussian kernel weights, paper eq. (1).

    Args:
      coords: (N, d) sensor positions.
      sigma: kernel width.
      kappa: connectivity radius; pairs farther than ``kappa`` get weight 0.

    Returns:
      (N, N) symmetric adjacency with zero diagonal.
    """
    d2 = jnp.sum(
        (coords[:, None, :] - coords[None, :, :]) ** 2, axis=-1
    )
    w = jnp.exp(-d2 / (2.0 * sigma**2))
    w = jnp.where(d2 <= kappa**2, w, 0.0)
    n = coords.shape[0]
    return w * (1.0 - jnp.eye(n, dtype=w.dtype))


def random_sensor_graph(
    key: jax.Array,
    n: int = 500,
    sigma: float = 0.074,
    kappa: float = 0.075,
) -> SensorGraph:
    """The paper's experimental network (Sec. V-B).

    ``n`` sensors placed uniformly at random in the unit square, weighted by
    the thresholded Gaussian kernel. Paper values: n=500, sigma=0.074 and a
    connectivity radius of 0.075 (see DESIGN.md for the kappa=0.600 erratum).
    """
    coords = jax.random.uniform(key, (n, 2))
    return SensorGraph(gaussian_kernel_weights(coords, sigma, kappa), coords)


def connected_sensor_graph(
    key: jax.Array,
    n: int = 500,
    sigma: float = 0.074,
    kappa: float = 0.075,
    max_tries: int = 50,
) -> SensorGraph:
    """Rejection-sample ``random_sensor_graph`` until connected.

    The paper assumes a connected graph (Sec. II); at its density
    (n=500, r=0.075) isolated islands occur in a small fraction of draws.
    """
    for i in range(max_tries):
        key, sub = jax.random.split(key)
        g = random_sensor_graph(sub, n, sigma, kappa)
        if is_connected(g.adjacency):
            return g
    raise RuntimeError(
        f"no connected graph in {max_tries} draws (n={n}, kappa={kappa})"
    )


def grid_graph(side: int, dtype=jnp.float32) -> SensorGraph:
    """4-neighbour unit-weight grid on ``side x side`` vertices."""
    n = side * side
    a = np.zeros((n, n), dtype=np.float64)
    for r in range(side):
        for c in range(side):
            i = r * side + c
            if c + 1 < side:
                a[i, i + 1] = a[i + 1, i] = 1.0
            if r + 1 < side:
                a[i, i + side] = a[i + side, i] = 1.0
    xs, ys = np.meshgrid(np.arange(side), np.arange(side), indexing="ij")
    coords = np.stack([xs.ravel(), ys.ravel()], axis=-1).astype(np.float64)
    coords /= max(side - 1, 1)
    return SensorGraph(jnp.asarray(a, dtype), jnp.asarray(coords, dtype))


def ring_graph(n: int, dtype=jnp.float32) -> SensorGraph:
    """Unit-weight ring C_n — the device-topology graph for gossip on a
    1-D mesh axis."""
    a = np.zeros((n, n), dtype=np.float64)
    idx = np.arange(n)
    a[idx, (idx + 1) % n] = 1.0
    a[(idx + 1) % n, idx] = 1.0
    return SensorGraph(jnp.asarray(a, dtype))


def torus_graph(rows: int, cols: int, dtype=jnp.float32) -> SensorGraph:
    """2-D torus — device-topology graph of a 2-axis mesh (ICI torus)."""
    n = rows * cols
    a = np.zeros((n, n), dtype=np.float64)
    for r in range(rows):
        for c in range(cols):
            i = r * cols + c
            for rr, cc in (((r + 1) % rows, c), (r, (c + 1) % cols)):
                j = rr * cols + cc
                if i != j:
                    a[i, j] = a[j, i] = 1.0
    return SensorGraph(jnp.asarray(a, dtype))


def degree_vector(adjacency: jax.Array) -> jax.Array:
    return jnp.sum(adjacency, axis=1)


def laplacian(adjacency: jax.Array) -> jax.Array:
    """Non-normalized graph Laplacian L = D - A (paper Sec. II)."""
    return jnp.diag(degree_vector(adjacency)) - adjacency


def lmax_upper_bound(adjacency: jax.Array) -> jax.Array:
    """Anderson--Morley bound: lambda_max <= max_{m~n} (d(m) + d(n)).

    This is the bound the paper proposes each node can compute with one
    neighbour exchange (Sec. IV-A, ref. [26]). Returns a scalar.
    """
    d = degree_vector(adjacency)
    pair = d[:, None] + d[None, :]
    mask = adjacency > 0
    return jnp.max(jnp.where(mask, pair, 0.0))


def lmax_power_iteration(
    laplacian_matrix: jax.Array,
    iters: int = 100,
    *,
    v0: jax.Array | None = None,
    seed: int = 0,
    return_vector: bool = False,
):
    """Tighter lambda_max estimate via power iteration (beyond-paper knob).

    A slightly inflated Rayleigh quotient (x1.01) keeps the Chebyshev domain
    valid even if the iteration has not fully converged.

    Args:
      v0: optional warm-start vector — e.g. the converged iterate from the
        previous topology, which the churn re-certification path carries
        across frames so a few iterations suffice after a small delta.
        Normalized internally; must not be the zero vector.
      seed: PRNG seed for the default start. The default is deterministic
        per seed (plus an alternating component so the start is not
        orthogonal to the top eigenspace on bipartite-ish graphs).
      return_vector: also return the final iterate, for reuse as the next
        call's ``v0``.

    Returns:
      The scalar estimate, or ``(estimate, vector)`` with ``return_vector``.
    """
    n = laplacian_matrix.shape[0]
    dtype = laplacian_matrix.dtype
    if v0 is None:
        v = jax.random.normal(jax.random.PRNGKey(seed), (n,), dtype)
        v = v / jnp.sqrt(n)
        # Alternating component: overlap with the top space on bipartite
        # graphs, where the top eigenvector is sign-alternating.
        v = v + jnp.where(jnp.arange(n) % 2 == 0, 1.0, -1.0) / n
    else:
        v = jnp.asarray(v0, dtype)
    v = v / (jnp.linalg.norm(v) + 1e-30)

    def body(_, v):
        w = laplacian_matrix @ v
        return w / (jnp.linalg.norm(w) + 1e-30)

    v = jax.lax.fori_loop(0, iters, body, v)
    lam = v @ (laplacian_matrix @ v) / (v @ v)
    est = 1.01 * lam
    if return_vector:
        return est, v
    return est


def is_connected(adjacency, *, ignore_isolated: bool = False) -> bool:
    """Host-side BFS connectivity check (the paper assumes connected G).

    Args:
      ignore_isolated: check connectivity of the subgraph induced on the
        non-isolated vertices only. The churn slot-pool model parks left
        (and not-yet-joined) sensors as isolated slots with every incident
        edge zeroed; those should not count against fleet connectivity.
        A graph with no edges at all is vacuously connected in this mode.
    """
    a = np.asarray(adjacency) > 0
    n = a.shape[0]
    has_edge = a.any(axis=1)
    if ignore_isolated:
        if not has_edge.any():
            return True
        start = int(np.argmax(has_edge))
    else:
        start = 0
    seen = np.zeros(n, dtype=bool)
    frontier = np.zeros(n, dtype=bool)
    frontier[start] = seen[start] = True
    while frontier.any():
        nxt = (a[frontier].any(axis=0)) & ~seen
        seen |= nxt
        frontier = nxt
    if ignore_isolated:
        return bool(seen[has_edge].all())
    return bool(seen.all())


def khop_neighborhood(adjacency, support, k: int) -> np.ndarray:
    """Boolean mask of vertices within ``k`` hops of ``support`` (host BFS).

    This is the locality set of the Chebyshev recurrence: a signal
    supported on S has ``T_k(L) f`` supported inside ``N_k(S)`` (every
    length-k walk from S stays within k hops of S), which is what lets the
    streaming layer filter a sparse frame delta on the induced submatrix of
    L over ``N_M(S)`` exactly (DESIGN.md Sec. 8).

    Args:
      adjacency: (N, N) weight matrix (only the zero pattern is used).
      support: (N,) boolean mask (or index array) of the seed set S.
      k: hop count >= 0.

    Returns:
      (N,) numpy boolean mask of ``N_k(S)``, including S itself.
    """
    a = np.asarray(adjacency) != 0.0
    n = a.shape[0]
    support = np.asarray(support)
    if support.dtype != np.bool_:
        mask = np.zeros(n, dtype=bool)
        mask[support] = True
    else:
        mask = support.copy()
    frontier = mask.copy()
    for _ in range(k):
        if not frontier.any():
            break
        reached = a[frontier].any(axis=0)
        frontier = reached & ~mask
        mask |= reached
    return mask


def spatial_partition_order(coords, n_parts: int) -> np.ndarray:
    """Order vertices so contiguous slabs form spatially-local partitions.

    Used by both the BSR kernel (block locality) and the distributed
    vertex-partitioned apply (small halos). Recursive coordinate bisection:
    sort by the widest axis, split in half, recurse. Returns a permutation
    of vertex ids; partition ``p`` owns ``order[p*N/P:(p+1)*N/P]``.
    """
    coords = np.asarray(coords)
    n = coords.shape[0]
    if n_parts <= 1:
        return np.arange(n)

    def rec(ids: np.ndarray, parts: int) -> np.ndarray:
        if parts == 1 or len(ids) <= 1:
            return ids
        c = coords[ids]
        axis = int(np.argmax(c.max(axis=0) - c.min(axis=0)))
        order = ids[np.argsort(c[:, axis], kind="stable")]
        left = parts // 2
        cut = len(ids) * left // parts
        return np.concatenate([rec(order[:cut], left), rec(order[cut:], parts - left)])

    with obs.span("graph.spatial_order", n=n):
        return rec(np.arange(n), n_parts)


def degree_order(adjacency) -> np.ndarray:
    """Order vertices without coordinates: by nonzeros a row, most first;
    ties keep label order, so isolated vertices come last.

    On a graph with skewed degrees (a Kronecker / R-MAT graph) this packs
    the hubs' rows and columns into the first block-rows and -columns,
    where their nonzeros share tiles. ``adjacency`` may be the Laplacian:
    its diagonal adds one to every vertex with an edge, which keeps the
    order. Returns a permutation of vertex ids.
    """
    deg = np.count_nonzero(np.asarray(adjacency), axis=1)
    return np.argsort(-deg, kind="stable")
