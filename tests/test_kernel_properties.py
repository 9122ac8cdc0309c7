"""Hypothesis property tests for the Pallas kernels (split from
test_kernels.py so the deterministic sweeps collect without the optional
``hypothesis`` dev dependency)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
st = pytest.importorskip("hypothesis.strategies")

from repro.kernels import ops, ref
from repro.kernels.cheb_bsr import cheb_step_pallas
from repro.core import chebyshev


def _random_bsr(key, n_rows, density, block, scale=1.0):
    """Block-CSR over a random tile mask of about ``density``, with the
    zero tiles that keep empty rows."""
    km, kb = jax.random.split(key)
    mask = np.asarray(jax.random.uniform(km, (n_rows, n_rows)) < density)
    rows, cols = np.nonzero(ref.stored_tiles(mask))
    blocks = scale * jax.random.normal(kb, (len(rows), block, block))
    blocks = blocks * jnp.asarray(mask[rows, cols], blocks.dtype)[:, None, None]
    return ref.BlockCsr(blocks, jnp.asarray(rows, jnp.int32), jnp.asarray(cols, jnp.int32))


@hypothesis.settings(max_examples=20, deadline=None)
@hypothesis.given(
    n_rows=st.integers(2, 8),
    density=st.floats(0.0, 1.0),
    block=st.sampled_from([8, 16]),
    f=st.sampled_from([8, 16]),
    seed=st.integers(0, 2**30),
)
def test_cheb_step_property(n_rows, density, block, f, seed):
    """Property: kernel == oracle for arbitrary Block-CSR structures."""
    key = jax.random.PRNGKey(seed)
    kb, k1, k2 = jax.random.split(key, 3)
    mat = _random_bsr(kb, n_rows, density, block)
    t1 = jax.random.normal(k1, (mat.n, f))
    t2 = jax.random.normal(k2, (mat.n, f))
    got = cheb_step_pallas(mat.blocks, mat.rows, mat.cols, t1, t2, alpha=2.5,
                           interpret=True)
    want = ref.cheb_step_ref(mat, t1, t2, 2.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@hypothesis.settings(max_examples=10, deadline=None)
@hypothesis.given(
    n_rows=st.integers(2, 6),
    density=st.floats(0.0, 1.0),
    order=st.integers(1, 12),
    eta=st.integers(1, 3),
    seed=st.integers(0, 2**30),
)
def test_cheb_apply_bsr_property(n_rows, density, order, eta, seed):
    """Property: the stepwise chain ``ops.cheb_apply_bsr`` == the jnp
    union oracle for arbitrary Block-CSR structures, orders (order 1
    returns before the scan), and union widths."""
    key = jax.random.PRNGKey(seed)
    kb, kf = jax.random.split(key)
    # 0.3: keep the recurrence numerically tame.
    mat = _random_bsr(kb, n_rows, density, 8, scale=0.3)
    f = jax.random.normal(kf, (mat.n, 8))
    coeffs = jnp.asarray(jax.random.normal(kf, (eta, order + 1)), f.dtype)
    lmax = 3.0
    got = ops.cheb_apply_bsr(mat.blocks, mat.rows, mat.cols, f, coeffs, lmax,
                             interpret=True)
    want = np.asarray(chebyshev.cheb_apply(
        lambda v: ref.bsr_matvec_ref(mat, v), f, coeffs, lmax))
    # A random block's spectrum leaves [0, lmax], where T_k grows with k:
    # f32 rounding then scales with the largest output, and so does atol.
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-3,
                               atol=2e-3 * max(1.0, float(np.max(np.abs(want)))))
