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


def _random_bsr(key, n_rows, per_row, block, dtype=jnp.float32):
    """Random Block-CSR matrix: ``per_row`` distinct block-columns in each
    block-row, random tiles."""
    mask = np.zeros((n_rows, n_rows), dtype=bool)
    for i in range(n_rows):
        mask[i, np.random.RandomState(i).choice(n_rows, size=per_row, replace=False)] = True
    return _bsr_of_mask(key, mask, block, dtype)


def _bsr_of_mask(key, mask, block, dtype=jnp.float32):
    """Block-CSR with random tiles wherever ``mask`` holds, and the zero
    tiles ``ref.stored_tiles`` adds to keep empty rows."""
    rows, cols = np.nonzero(ref.stored_tiles(mask))
    blocks = jax.random.normal(key, (len(rows), block, block), dtype)
    blocks = blocks * jnp.asarray(mask[rows, cols], dtype)[:, None, None]
    return ref.BlockCsr(blocks, jnp.asarray(rows, jnp.int32), jnp.asarray(cols, jnp.int32))


def _laplacian_bsr(n=96, block=8, seed=0):
    g = graph.connected_sensor_graph(
        jax.random.PRNGKey(seed), n=n, sigma=0.17, kappa=0.18)
    lap = np.asarray(g.laplacian())
    order = graph.spatial_partition_order(np.asarray(g.coords),
                                          max(n // block, 1))
    lap = lap[np.ix_(order, order)]
    return ref.bsr_from_dense(lap, block), lap, float(g.lmax_bound())


def test_bsr_from_dense_roundtrip():
    mat, lap, _ = _laplacian_bsr()
    dense = np.asarray(ref.bsr_to_dense(mat))
    n = lap.shape[0]
    np.testing.assert_allclose(dense[:n, :n], lap, atol=1e-6)
    assert np.all(dense[n:, :] == 0) and np.all(dense[:, n:] == 0)


@pytest.mark.parametrize("block,f,ftile", [(8, 8, 8), (8, 32, 16), (16, 128, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_cheb_step_matches_ref(block, f, ftile, dtype):
    key = jax.random.PRNGKey(0)
    mat = _random_bsr(key, n_rows=6, per_row=3, block=block, dtype=dtype)
    k1, k2 = jax.random.split(key)
    t1 = jax.random.normal(k1, (mat.n, f), dtype)
    t2 = jax.random.normal(k2, (mat.n, f), dtype)
    alpha = 3.7
    for first in (False, True):
        got = cheb_step_pallas(
            mat.blocks, mat.rows, mat.cols, t1, t2,
            alpha=alpha, first=first, f_tile=ftile, interpret=True)
        want = ref.cheb_step_ref(mat, t1, t2, alpha, first=first)
        tol = 1e-5 if dtype == jnp.float32 else 5e-2
        np.testing.assert_allclose(
            np.asarray(got, np.float64), np.asarray(want, np.float64),
            rtol=tol, atol=tol)


def _masks() -> dict[str, np.ndarray]:
    """Tile masks of 7 block-rows: one empty row, one hub row holding a
    tile in every column, and rows of a single tile."""
    rng = np.random.default_rng(11)
    mixed = rng.random((7, 7)) < 0.35
    mixed[3] = False
    hub = np.eye(7, dtype=bool)
    hub[0] = True
    hub[:, 0] = True
    single = np.zeros((7, 7), dtype=bool)
    single[np.arange(7), (3 * np.arange(7) + 2) % 7] = True
    return {"empty_row": mixed, "hub_row": hub, "single_tile_rows": single}


@pytest.mark.parametrize("name", sorted(_masks()))
def test_stored_tile_step_matches_dense(name):
    """One grid step per stored tile: the step kernel equals the eq. 9
    step with the densified matrix, in float64; a row with no nonzero
    tile still gets its ``-2 t1 - t2`` (its zero tile is stored)."""
    mask = _masks()[name]
    mat = _bsr_of_mask(jax.random.PRNGKey(5), mask, 8)
    assert mat.n_tiles == int(ref.stored_tiles(mask).sum())
    assert mat.n_tiles == mask.sum() + (~mask.any(axis=1)).sum()
    lap = np.asarray(ref.bsr_to_dense(mat), np.float64)
    k1, k2 = jax.random.split(jax.random.PRNGKey(6))
    t1 = jax.random.normal(k1, (mat.n, 16))
    t2 = jax.random.normal(k2, (mat.n, 16))
    a1, a2 = np.asarray(t1, np.float64), np.asarray(t2, np.float64)
    for first in (False, True):
        got = cheb_step_pallas(mat.blocks, mat.rows, mat.cols, t1, t2,
                               alpha=2.5, first=first, interpret=True)
        want = (lap @ a1 / 2.5 - a1) if first else (lap @ a1 / 1.25 - 2.0 * a1 - a2)
        np.testing.assert_allclose(np.asarray(got, np.float64), want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("f", [8, 128, 256])
@pytest.mark.parametrize("key", sorted(autotune._F_TILE_TABLE))
def test_select_tiling_returns_lowerable_f_tile(key, f):
    """Pallas TPU takes a block whose last dim is a multiple of 128 or the
    whole dim: every F tile ``choose_f_tile`` answers is one, and it is
    the widest such tile the table prefers for the key."""
    block, dtype = key
    t = autotune.choose_f_tile(f, block, dtype)
    assert f % t == 0, (key, f, t)
    assert t % 128 == 0 or t == f, (key, f, t)
    wider = [c for c in autotune._F_TILE_TABLE[key] if c > t]
    assert all(f % c or (c % 128 and c != f) for c in wider), (key, f, t)


def test_full_apply_matches_dense_oracle():
    mat, lap, lmax = _laplacian_bsr(n=96, block=8)
    bank = [multipliers.heat(0.6), multipliers.tikhonov(1.0, 1)]
    coeffs = chebyshev.cheb_coefficients(bank, order=15, lmax=lmax)
    f = jax.random.normal(jax.random.PRNGKey(3), (mat.n, 8))
    got = ops.cheb_apply_bsr(
        mat.blocks, mat.rows, mat.cols, f, coeffs, lmax, interpret=True)
    want = ref.cheb_apply_bsr_ref(mat, f, coeffs, lmax)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_full_apply_agrees_with_core_dense_path():
    # Kernel path == core dense path on the unpadded region.
    mat, lap, lmax = _laplacian_bsr(n=64, block=8)
    coeffs = chebyshev.cheb_coefficients([multipliers.heat(1.0)], 12, lmax)
    n = lap.shape[0]
    f = jax.random.normal(jax.random.PRNGKey(4), (mat.n, 4))
    f = f.at[n:].set(0.0)
    got = ops.cheb_apply_bsr(mat.blocks, mat.rows, mat.cols, f, coeffs, lmax,
                             interpret=True)
    dense = chebyshev.cheb_apply_dense(jnp.asarray(lap), f[:n], coeffs, lmax)
    np.testing.assert_allclose(np.asarray(got)[:, :n], np.asarray(dense),
                               rtol=2e-4, atol=2e-4)


def test_kernel_linearity_property():
    """Phi~ is linear: kernel(a f + b g) == a kernel(f) + b kernel(g)."""
    mat, _, lmax = _laplacian_bsr(n=64, block=8)
    coeffs = chebyshev.cheb_coefficients([multipliers.heat(0.5)], 10, lmax)
    kf, kg = jax.random.split(jax.random.PRNGKey(9))
    f = jax.random.normal(kf, (mat.n, 4))
    g = jax.random.normal(kg, (mat.n, 4))
    ops_ = (mat.blocks, mat.rows, mat.cols)
    lhs = ops.cheb_apply_bsr(*ops_, 2.0 * f - 3.0 * g, coeffs, lmax, interpret=True)
    rhs = (2.0 * ops.cheb_apply_bsr(*ops_, f, coeffs, lmax, interpret=True)
           - 3.0 * ops.cheb_apply_bsr(*ops_, g, coeffs, lmax, interpret=True))
    np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_cheb_step_tpu_production_shapes():
    """TPU-aligned BlockSpec shapes (128x128 tiles, F=256) in interpret
    mode — validates the exact tiling the production kernel would run."""
    key = jax.random.PRNGKey(42)
    mat = _random_bsr(key, n_rows=4, per_row=3, block=128, dtype=jnp.bfloat16)
    k1, k2 = jax.random.split(key)
    t1 = jax.random.normal(k1, (mat.n, 256), jnp.bfloat16)
    t2 = jax.random.normal(k2, (mat.n, 256), jnp.bfloat16)
    got = cheb_step_pallas(mat.blocks, mat.rows, mat.cols, t1, t2,
                           alpha=4.0, f_tile=128, interpret=True)
    want = ref.cheb_step_ref(mat, t1, t2, 4.0)
    g = np.asarray(got, np.float64)
    w = np.asarray(want, np.float64)
    scale = np.max(np.abs(w)) + 1e-9
    assert np.max(np.abs(g - w)) / scale < 2e-2
