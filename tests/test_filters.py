"""Backend parity matrix for the unified ``GraphFilter`` layer.

Acceptance contract: every registered backend reachable through
``GraphFilter.apply`` matches the dense jnp oracle within 1e-5 (f32) on a
random sensor graph (grid backend: on its native grid topology), for both
(N,) and (N, F) signals, and the ``bsr`` apply runs the Pallas step
kernel and no other. Multi-device behaviour of the distributed backends is
exercised in a forced-8-device subprocess (slow)."""

import re
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import chebyshev, graph, multipliers, operators
from repro.filters import (
    GraphFilter,
    available_backends,
    backend_is_traceable,
    get_backend,
)
from repro.kernels import autotune

REPO = Path(__file__).resolve().parents[1]

SENSOR_BACKENDS = ("bsr", "halo", "allgather")


@pytest.fixture(scope="module")
def sensor_setting():
    g = graph.connected_sensor_graph(
        jax.random.PRNGKey(1), n=96, sigma=0.17, kappa=0.18)
    bank = [multipliers.tikhonov(1.0, 1), multipliers.heat(0.5)]
    filt = GraphFilter.from_multipliers(bank, order=16, graph=g)
    f = jax.random.normal(jax.random.PRNGKey(2), (g.n_vertices, 8))
    return g, filt, f


@pytest.fixture(scope="module")
def grid_setting():
    g = graph.grid_graph(16)
    bank = [multipliers.tikhonov(1.0, 1), multipliers.heat(0.5)]
    filt = GraphFilter.from_multipliers(bank, order=12, graph=g, lmax=8.0)
    f = jax.random.normal(jax.random.PRNGKey(3), (g.n_vertices, 4))
    return g, filt, f


def test_all_five_backends_registered():
    for name in ("dense", "bsr", "halo", "allgather", "grid"):
        assert name in available_backends(), name
        assert get_backend(name).name == name


def test_unknown_backend_raises(sensor_setting):
    _, filt, f = sensor_setting
    with pytest.raises(KeyError, match="unknown filter backend"):
        filt.apply(f, backend="nope")


@pytest.mark.parametrize("backend", SENSOR_BACKENDS)
@pytest.mark.parametrize("batched", [True, False])
def test_backend_parity_vs_dense(sensor_setting, backend, batched):
    """bsr + distributed backends match cheb_apply_dense within 1e-5."""
    g, filt, f = sensor_setting
    sig = f if batched else f[:, 0]
    want = chebyshev.cheb_apply_dense(
        g.laplacian(), sig, jnp.asarray(filt.coeffs, sig.dtype), filt.lmax)
    got = filt.apply(sig, backend=backend)
    assert got.shape == (filt.eta,) + sig.shape
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("batched", [True, False])
def test_grid_backend_parity_vs_dense(grid_setting, batched):
    g, filt, f = grid_setting
    sig = f if batched else f[:, 0]
    want = chebyshev.cheb_apply_dense(
        g.laplacian(), sig, jnp.asarray(filt.coeffs, sig.dtype), filt.lmax)
    got = filt.apply(sig, backend="grid")
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", ["bsr", "halo", "grid"])
def test_adjoint_parity_vs_dense(sensor_setting, grid_setting, backend):
    g, filt, f = grid_setting if backend == "grid" else sensor_setting
    a = filt.apply(f, backend="dense")
    want = filt.adjoint(a, backend="dense")
    got = filt.adjoint(a, backend=backend)
    assert got.shape == f.shape
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", ["dense", "bsr", "halo"])
def test_gram_equals_adjoint_of_apply(sensor_setting, backend):
    """Sec. IV-C: the single degree-2M gram filter == Phi~*(Phi~ f)."""
    _, filt, f = sensor_setting
    composed = filt.adjoint(filt.apply(f, backend=backend), backend=backend)
    gram = filt.gram(f, backend=backend)
    np.testing.assert_allclose(
        np.asarray(gram), np.asarray(composed), rtol=5e-4, atol=5e-4)


# ------------------------------------------------ exact-oracle parity --
#
# Polynomial multipliers of degree <= order make the truncated Chebyshev
# expansion *exact* (quadrature included), so every backend's apply /
# adjoint / gram must match the eigendecomposition oracle
# (core/operators.exact_union_apply and friends) to float tolerance — not
# just match each other. This pins adjoint and gram, which the rest of
# the suite exercises far less than apply, on ALL registered backends
# including grid.

POLY_BANK = [
    lambda x: 0.3 + 0.1 * np.asarray(x, np.float64),
    lambda x: 1.0 - 0.25 * np.asarray(x, np.float64)
    + 0.05 * np.asarray(x, np.float64) ** 2,
]


def _oracle_filter_and_graph(backend):
    if backend == "grid":
        g = graph.grid_graph(16)
        lmax = 8.0
    else:
        g = graph.connected_sensor_graph(
            jax.random.PRNGKey(9), n=96, sigma=0.17, kappa=0.18)
        lmax = float(g.lmax_bound())
    filt = GraphFilter.from_multipliers(POLY_BANK, order=8, graph=g,
                                        lmax=lmax)
    opts = {}
    if backend == "matvec":
        # tensordot, not @: the adjoint recurrence carries the eta blocks
        # in trailing dims, so the closure must contract the vertex axis.
        lap = g.laplacian()
        opts["matvec"] = lambda v: jnp.tensordot(lap, v, axes=1)
    return g, filt, opts


@pytest.mark.parametrize("backend", sorted(
    ("dense", "bsr", "halo", "allgather", "grid", "matvec")))
def test_apply_matches_exact_oracle(backend):
    g, filt, opts = _oracle_filter_and_graph(backend)
    f = jax.random.normal(jax.random.PRNGKey(10), (g.n_vertices, 4))
    want = operators.exact_union_apply(
        np.asarray(g.laplacian(), np.float64), POLY_BANK, np.asarray(f))
    got = filt.apply(f, backend=backend, **opts)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("backend", sorted(
    ("dense", "bsr", "halo", "allgather", "grid", "matvec")))
def test_adjoint_matches_exact_oracle(backend):
    """``Phi~* a = sum_j Psi_j a_j`` (symmetric Psi_j) vs the eigh oracle."""
    g, filt, opts = _oracle_filter_and_graph(backend)
    a = jax.random.normal(jax.random.PRNGKey(11),
                          (filt.eta, g.n_vertices, 4))
    mats = operators.exact_multiplier_matrix(
        np.asarray(g.laplacian(), np.float64), POLY_BANK)
    want = np.einsum("jnm,jmf->nf", mats, np.asarray(a, np.float64))
    got = filt.adjoint(a, backend=backend, **opts)
    assert got.shape == (g.n_vertices, 4)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("backend", sorted(
    ("dense", "bsr", "halo", "allgather", "grid", "matvec")))
def test_gram_matches_exact_oracle(backend):
    """The single degree-2M gram filter vs ``sum_j Psi_j^2 f`` (eigh)."""
    g, filt, opts = _oracle_filter_and_graph(backend)
    f = jax.random.normal(jax.random.PRNGKey(12), (g.n_vertices, 4))
    mats = operators.exact_multiplier_matrix(
        np.asarray(g.laplacian(), np.float64), POLY_BANK)
    want = sum(m @ (m @ np.asarray(f, np.float64)) for m in mats)
    got = filt.gram(f, backend=backend, **opts)
    assert got.shape == f.shape
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4,
                               atol=2e-4)


def test_traceable_flags_match_backend_contract():
    """The capability flag that replaced apps/denoising's hardcoded
    backend-name tuple: compiled-loop backends declare it, host-staging
    backends do not."""
    want = {"dense": True, "bsr": True, "matvec": True,
            "halo": False, "allgather": False, "grid": False}
    for name, flag in want.items():
        assert backend_is_traceable(name) == flag, name


def test_matvec_backend_matches_dense(sensor_setting):
    g, filt, f = sensor_setting
    lap = g.laplacian()
    got = filt.apply(f, backend="matvec", matvec=lambda v: lap @ v)
    want = filt.apply(f, backend="dense")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_graphless_filter_requires_graph(sensor_setting):
    filt = GraphFilter.from_coefficients(
        np.ones((1, 5)), lmax=2.0)
    with pytest.raises(ValueError, match="bound graph"):
        filt.apply(jnp.ones((8,)), backend="dense")


def test_bsr_apply_runs_only_the_step_kernel(sensor_setting):
    """``GraphFilter.apply(backend="bsr")`` traces exactly two
    ``pallas_call``s, both the step kernel: the first order and the scan
    body, which runs the remaining orders."""
    _, filt, f = sensor_setting
    filt.prepare_backend("bsr")  # host-side prepare, outside the trace
    jaxpr = str(jax.make_jaxpr(lambda x: filt.apply(x, backend="bsr"))(f))
    assert jaxpr.count("pallas_call") == 2
    assert re.findall(r"cheb_bsr_\w+", jaxpr) == ["cheb_bsr_step"] * 2


def test_bsr_two_f_tiles_match_dense(sensor_setting):
    """F=256 at block 8 runs the step kernel over two 128-wide F tiles;
    each tile's columns match the dense oracle."""
    g, filt, _ = sensor_setting
    assert autotune.choose_f_tile(256, 8) == 128
    f = jax.random.normal(jax.random.PRNGKey(7), (g.n_vertices, 256))
    want = chebyshev.cheb_apply_dense(
        g.laplacian(), f, jnp.asarray(filt.coeffs, f.dtype), filt.lmax)
    got = filt.apply(f, backend="bsr", block_size=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_backend_state_is_cached(sensor_setting):
    _, filt, f = sensor_setting
    be = get_backend("bsr")
    s1 = filt._backend_state(be, {})
    s2 = filt._backend_state(be, {})
    assert s1 is s2
    s3 = filt._backend_state(be, {"block_size": 16})
    assert s3 is not s1


def test_messages_per_apply_bounds(sensor_setting):
    """Paper Sec. IV-A: halo words never exceed the 2M|E| radio bound;
    single-device backends report zero network words."""
    g, filt, _ = sensor_setting
    m = filt.order
    assert filt.messages_per_apply(backend="dense") == 0
    assert filt.messages_per_apply(backend="bsr") == 0
    halo = filt.messages_per_apply(backend="halo")
    assert 0 <= halo <= 2 * m * g.n_edges


def test_grid_backend_rejects_non_grid_graph():
    """A square-N non-grid graph must be refused, not silently filtered."""
    g = graph.ring_graph(256)  # N = 16^2 but degrees are all 2
    filt = GraphFilter.from_multipliers(
        [multipliers.heat(0.5)], 8, graph=g, lmax=4.0)
    with pytest.raises(ValueError, match="4-neighbour"):
        filt.apply(jnp.ones((256,)), backend="grid")


def test_filter_identity_semantics(sensor_setting):
    """eq=False: filters hash/compare by identity (usable as dict keys)."""
    _, filt, _ = sensor_setting
    assert filt == filt and {filt: 1}[filt] == 1
    other = GraphFilter.from_coefficients(filt.coeffs, filt.lmax)
    assert filt != other


def test_graph_filter_engine_batches(sensor_setting):
    """Serving layer: panel batching answers every request with the same
    result as a solo dense apply."""
    from repro.serve import GraphFilterEngine

    g, filt, _ = sensor_setting
    eng = GraphFilterEngine(filt, backend="bsr", panel_width=4)
    signals = [np.random.RandomState(i).randn(g.n_vertices).astype(np.float32)
               for i in range(6)]
    results = []
    for s in signals:
        got = eng.submit(s)
        if got:
            results.extend(got)
    tail = eng.flush()
    if tail:
        results.extend(tail)
    assert len(results) == 6 and eng.applies == 2 and eng.served == 6
    for s, r in zip(signals, results):
        want = np.asarray(filt.apply(jnp.asarray(s), backend="dense"))
        np.testing.assert_allclose(r, want, rtol=1e-5, atol=1e-5)


SUBPROCESS_PARITY = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.core import chebyshev, graph, multipliers
from repro.filters import GraphFilter

g = graph.connected_sensor_graph(jax.random.PRNGKey(4), n=200,
                                 sigma=0.12, kappa=0.125)
filt = GraphFilter.from_multipliers(
    [multipliers.tikhonov(1.0, 1), multipliers.heat(0.5)], 16, graph=g)
f = jax.random.normal(jax.random.PRNGKey(5), (g.n_vertices, 4))
want = np.asarray(filt.apply(f, backend="dense"))
for be in ("halo", "allgather"):
    got = np.asarray(filt.apply(f, backend=be))
    err = np.max(np.abs(got - want))
    assert err < 1e-5, (be, err)
    print(be, err)
assert filt.messages_per_apply(backend="halo") <= 2 * 16 * g.n_edges
assert (filt.messages_per_apply(backend="halo")
        < filt.messages_per_apply(backend="allgather"))

gg = graph.grid_graph(32)
gf = GraphFilter.from_multipliers([multipliers.heat(0.5)], 12,
                                  graph=gg, lmax=8.0)
x = jax.random.normal(jax.random.PRNGKey(6), (gg.n_vertices, 4))
err = float(jnp.max(jnp.abs(gf.apply(x, backend="grid")
                            - gf.apply(x, backend="dense"))))
assert err < 1e-5, err
print("grid", err)
print("OK")
"""


@pytest.mark.slow
def test_distributed_backend_parity_8_devices():
    env = {"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin"}
    proc = subprocess.run(
        [sys.executable, "-c", SUBPROCESS_PARITY],
        capture_output=True, text=True, env=env, timeout=900)
    assert proc.returncode == 0, f"{proc.stdout}\n{proc.stderr}"
    assert "OK" in proc.stdout
