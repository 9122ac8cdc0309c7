"""Pallas kernel validation: interpret-mode vs pure-jnp oracle across
shape/dtype sweeps.

The hypothesis property-based cases live in ``test_kernel_properties.py``
(own module so this one collects even without the optional ``hypothesis``
dev dependency — see requirements-dev.txt).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import chebyshev, graph, multipliers
from repro.kernels import autotune, ops, ref
from repro.kernels.cheb_bsr import cheb_step_pallas


def _random_bell(key, n_rows, k_max, block, dtype=jnp.float32, sym=True):
    """Random Block-ELL matrix with valid (sorted, in-range) columns."""
    kb, kc = jax.random.split(key)
    blocks = jax.random.normal(kb, (n_rows, k_max, block, block), dtype)
    cols = np.stack([
        np.random.RandomState(i).choice(n_rows, size=k_max, replace=False)
        for i in range(n_rows)
    ]).astype(np.int32)
    return ref.BlockEll(blocks, jnp.asarray(cols))


def _laplacian_bell(n=96, block=8, seed=0):
    g = graph.connected_sensor_graph(
        jax.random.PRNGKey(seed), n=n, sigma=0.17, kappa=0.18)
    lap = np.asarray(g.laplacian())
    order = graph.spatial_partition_order(np.asarray(g.coords),
                                          max(n // block, 1))
    lap = lap[np.ix_(order, order)]
    return ref.bsr_from_dense(lap, block), lap, float(g.lmax_bound())


def test_bsr_from_dense_roundtrip():
    bell, lap, _ = _laplacian_bell()
    dense = np.asarray(ref.bsr_to_dense(bell))
    n = lap.shape[0]
    np.testing.assert_allclose(dense[:n, :n], lap, atol=1e-6)
    assert np.all(dense[n:, :] == 0) and np.all(dense[:, n:] == 0)


@pytest.mark.parametrize("block,f,ftile", [(8, 8, 8), (8, 32, 16), (16, 128, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_cheb_step_matches_ref(block, f, ftile, dtype):
    key = jax.random.PRNGKey(0)
    bell = _random_bell(key, n_rows=6, k_max=3, block=block, dtype=dtype)
    k1, k2 = jax.random.split(key)
    t1 = jax.random.normal(k1, (bell.n, f), dtype)
    t2 = jax.random.normal(k2, (bell.n, f), dtype)
    alpha = 3.7
    for first in (False, True):
        got = cheb_step_pallas(
            bell.blocks, bell.cols, t1, t2,
            alpha=alpha, first=first, f_tile=ftile, interpret=True)
        want = ref.cheb_step_ref(bell, t1, t2, alpha, first=first)
        tol = 1e-5 if dtype == jnp.float32 else 5e-2
        np.testing.assert_allclose(
            np.asarray(got, np.float64), np.asarray(want, np.float64),
            rtol=tol, atol=tol)


@pytest.mark.parametrize("f", [8, 128, 256])
@pytest.mark.parametrize("key", sorted(autotune._F_TILE_TABLE))
def test_select_tiling_returns_lowerable_f_tile(key, f):
    """Pallas TPU takes a block whose last dim is a multiple of 128 or the
    whole dim: every f_tile select_tiling answers, fused or not, is one."""
    block, dtype = key
    for n in (256, 4096, 16384):
        for krylov in (jnp.float32, jnp.bfloat16):
            t = autotune.select_tiling(n, f, 5, n // block, 8, block, dtype,
                                       krylov_dtype=krylov)
            assert f % t.f_tile == 0, (n, krylov, t)
            assert t.f_tile % 128 == 0 or t.f_tile == f, (n, krylov, t)


def test_full_apply_matches_dense_oracle():
    bell, lap, lmax = _laplacian_bell(n=96, block=8)
    bank = [multipliers.heat(0.6), multipliers.tikhonov(1.0, 1)]
    coeffs = chebyshev.cheb_coefficients(bank, order=15, lmax=lmax)
    f = jax.random.normal(jax.random.PRNGKey(3), (bell.n, 8))
    got = ops.cheb_apply_bsr(
        bell.blocks, bell.cols, f, coeffs, lmax, interpret=True)
    want = ref.cheb_apply_bsr_ref(bell, f, coeffs, lmax)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_full_apply_agrees_with_core_dense_path():
    # Kernel path == core dense path on the unpadded region.
    bell, lap, lmax = _laplacian_bell(n=64, block=8)
    coeffs = chebyshev.cheb_coefficients([multipliers.heat(1.0)], 12, lmax)
    n = lap.shape[0]
    f = jax.random.normal(jax.random.PRNGKey(4), (bell.n, 4))
    f = f.at[n:].set(0.0)
    got = ops.cheb_apply_bsr(bell.blocks, bell.cols, f, coeffs, lmax,
                             interpret=True)
    dense = chebyshev.cheb_apply_dense(jnp.asarray(lap), f[:n], coeffs, lmax)
    np.testing.assert_allclose(np.asarray(got)[:, :n], np.asarray(dense),
                               rtol=2e-4, atol=2e-4)


def test_kernel_linearity_property():
    """Phi~ is linear: kernel(a f + b g) == a kernel(f) + b kernel(g)."""
    bell, _, lmax = _laplacian_bell(n=64, block=8)
    coeffs = chebyshev.cheb_coefficients([multipliers.heat(0.5)], 10, lmax)
    kf, kg = jax.random.split(jax.random.PRNGKey(9))
    f = jax.random.normal(kf, (bell.n, 4))
    g = jax.random.normal(kg, (bell.n, 4))
    lhs = ops.cheb_apply_bsr(bell.blocks, bell.cols, 2.0 * f - 3.0 * g,
                             coeffs, lmax, interpret=True)
    rhs = (2.0 * ops.cheb_apply_bsr(bell.blocks, bell.cols, f, coeffs, lmax,
                                    interpret=True)
           - 3.0 * ops.cheb_apply_bsr(bell.blocks, bell.cols, g, coeffs,
                                      lmax, interpret=True))
    np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_cheb_step_tpu_production_shapes():
    """TPU-aligned BlockSpec shapes (128x128 tiles, F=256) in interpret
    mode — validates the exact tiling the production kernel would run."""
    key = jax.random.PRNGKey(42)
    bell = _random_bell(key, n_rows=4, k_max=3, block=128,
                        dtype=jnp.bfloat16)
    k1, k2 = jax.random.split(key)
    t1 = jax.random.normal(k1, (bell.n, 256), jnp.bfloat16)
    t2 = jax.random.normal(k2, (bell.n, 256), jnp.bfloat16)
    got = cheb_step_pallas(bell.blocks, bell.cols, t1, t2,
                           alpha=4.0, f_tile=128, interpret=True)
    want = ref.cheb_step_ref(bell, t1, t2, 4.0)
    g = np.asarray(got, np.float64)
    w = np.asarray(want, np.float64)
    scale = np.max(np.abs(w)) + 1e-9
    assert np.max(np.abs(g - w)) / scale < 2e-2
