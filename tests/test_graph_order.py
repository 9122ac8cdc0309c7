"""The ``bsr`` backend on a graph without coordinates: the vertex order it
builds its tiles on (``graph.degree_order``), and its apply on a seeded
Kronecker (R-MAT) graph against the eigendecomposition, on the CPU."""

from __future__ import annotations

import math

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import graph, multipliers
from repro.filters import GraphFilter, get_backend
from repro.kernels import autotune, ref


def _kronecker(scale: int, seed: int, edgefactor: int = 16) -> graph.SensorGraph:
    """A Graph500-style Kronecker graph: initiator 0.57/0.19/0.19/0.05,
    ``edgefactor * 2^scale`` draws, labels permuted, self-loops dropped,
    duplicates merged, unit weights, no coordinates."""
    n, m = 1 << scale, edgefactor << scale
    rng = np.random.default_rng(seed)
    i = np.zeros(m, np.int64)
    j = np.zeros(m, np.int64)
    for bit in range(scale):
        i_bit = rng.random(m) > 0.76
        j_bit = rng.random(m) > np.where(i_bit, 0.19 / 0.24, 0.57 / 0.76)
        i += i_bit.astype(np.int64) << bit
        j += j_bit.astype(np.int64) << bit
    perm = rng.permutation(n)
    i, j = perm[i], perm[j]
    keep = i != j
    adj = np.zeros((n, n), np.float32)
    adj[i[keep], j[keep]] = adj[j[keep], i[keep]] = 1.0
    return graph.SensorGraph(jax.numpy.asarray(adj))


@pytest.fixture(scope="module")
def kron9() -> graph.SensorGraph:
    return _kronecker(9, seed=3)


def _stored(lap: np.ndarray, perm: np.ndarray, block: int) -> int:
    return int(ref.stored_tiles(ref.permuted_tile_mask(lap, perm, block)).sum())


def test_degree_order_is_a_permutation_with_isolated_vertices_last(kron9):
    adj = np.asarray(kron9.adjacency)
    deg = np.count_nonzero(adj, axis=1)
    perm = graph.degree_order(adj)
    assert sorted(perm) == list(range(kron9.n_vertices))
    assert np.all(np.diff(deg[perm]) <= 0)  # most nonzeros first
    isolated = np.flatnonzero(deg == 0)
    assert len(isolated) > 0  # the skewed draw leaves some vertices out
    np.testing.assert_array_equal(perm[-len(isolated):], isolated)  # kept, in label order
    # The Laplacian's diagonal keeps the order.
    np.testing.assert_array_equal(graph.degree_order(np.asarray(kron9.laplacian())), perm)


@pytest.mark.parametrize("block", [8, 16, 32, 64])
def test_degree_order_stores_fewer_tiles_than_the_identity(kron9, block):
    lap = np.asarray(kron9.laplacian())
    perm = graph.degree_order(lap)
    ident = np.arange(kron9.n_vertices)
    assert _stored(lap, perm, block) < _stored(lap, ident, block)


def test_bsr_prepare_orders_a_graph_without_coordinates(kron9):
    filt = GraphFilter.from_multipliers([multipliers.heat(1.0)], order=8, graph=kron9)
    state = get_backend("bsr").prepare(filt)
    np.testing.assert_array_equal(state.perm, graph.degree_order(kron9.adjacency))
    lap = np.asarray(kron9.laplacian())
    dense = np.asarray(ref.bsr_to_dense(state.mat))
    np.testing.assert_array_equal(dense[:state.n, :state.n], lap[np.ix_(state.perm, state.perm)])


def test_sensor_field_order_is_unchanged():
    """A graph with coordinates keeps the spatial bisection order, bit for
    bit: ``spatial_partition_order`` into the power-of-two count of parts
    of finest slabs, as before the coordinate-free order existed."""
    g = graph.random_sensor_graph(jax.random.PRNGKey(4), n=700, sigma=0.08, kappa=0.08)
    filt = GraphFilter.from_multipliers([multipliers.heat(1.0)], order=8, graph=g)
    state = get_backend("bsr").prepare(filt)
    parts = 1 << (math.ceil(700 / autotune.BLOCK_CANDIDATES[0]) - 1).bit_length()
    np.testing.assert_array_equal(
        state.perm, graph.spatial_partition_order(np.asarray(g.coords), parts))


def test_order_span_names_the_method(kron9, tmp_path):
    """``bsr.order`` carries the method and the vertex count."""
    from jax.profiler import ProfileData
    import glob

    field = graph.random_sensor_graph(jax.random.PRNGKey(5), n=300, sigma=0.12, kappa=0.12)
    obs.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for g in (kron9, field):
            filt = GraphFilter.from_multipliers([multipliers.heat(1.0)], order=4, graph=g)
            get_backend("bsr").prepare(filt)
    finally:
        jax.profiler.stop_trace()
        obs.disable()
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))[0]
    args = [dict(e.stats) for p in ProfileData.from_file(path).planes for ln in p.lines
            for e in ln.events if e.name == "repro.bsr.order"]
    assert [(a["method"], a["n"]) for a in args] == [("degree", 512), ("spatial", 300)]


@pytest.mark.parametrize("opts", [{}, {"block_size": 32}],
                         ids=["chosen", "stepwise_block32"])
def test_bsr_on_a_kronecker_graph_matches_the_eigendecomposition(kron9, opts):
    """``GraphFilter.apply(backend="bsr")`` at scale 9 without coordinates
    against the exact filter from ``eigh`` in float64: at the block the
    program chooses, and through the stepwise kernel at block 32, where
    the hub rows hold many more tiles than the rest.

    Tolerance 1e-5 of the largest output: the kernels compute in float32
    at HIGHEST, and a hub row sums a few hundred float32 products at each
    of the 20 orders; the coefficients are those of the expansion itself,
    so the oracle carries no approximation error."""
    lmax = float(kron9.lmax_bound())
    bank = multipliers.sgwt_filter_bank(lmax, 3)
    filt = GraphFilter.from_multipliers(bank, order=20, graph=kron9, lmax=lmax)
    f = np.random.default_rng(9).normal(size=(kron9.n_vertices, 16)).astype(np.float32)
    got = np.asarray(filt.apply(f, backend="bsr", **opts), np.float64)

    lam, u = np.linalg.eigh(np.asarray(kron9.laplacian(), np.float64))
    x = np.clip(2.0 * lam / lmax - 1.0, -1.0, 1.0)
    c = np.asarray(filt.coeffs, np.float64)
    t = np.cos(np.arange(c.shape[1])[:, None] * np.arccos(x))
    resp = c @ t - 0.5 * c[:, :1]  # (eta, N): sum_k c_k T_k(x), c_0 halved
    want = np.einsum("vi,ji,iw->jvw", u, resp, u.T @ f.astype(np.float64))
    err = np.max(np.abs(got - want)) / np.max(np.abs(want))
    assert err < 1e-5, err
