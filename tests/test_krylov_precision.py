"""Precision tests for the bf16 Krylov-buffer mode (DESIGN.md Sec. 6.3).

Pins three contracts of ``krylov_dtype`` on the ``bsr`` stepwise chain,
at block 8 and at block 32, where a block-row holds several stored
tiles:

* ``krylov_dtype="bfloat16"`` stays within the documented error bound
  ``||bf16 - f32||_inf <= 16 * 2^-8 * ||f32||_inf`` across orders
  M in {5, 20, 80}, and the error does not grow with M (the shifted
  recurrence keeps ``|Tbar_k| <= 1``, so rounding does not compound);
* the default f32 path is bit-identical to passing
  ``krylov_dtype="float32"``: the casts are no-ops;
* an order-1 filter, which stores no Krylov vector, matches ``dense``
  in either mode.
"""

import jax
import numpy as np
import pytest

from repro.core import graph, multipliers
from repro.filters import GraphFilter

# DESIGN.md Sec. 6.3: every stored T_k has |entries| <= ||f||_inf (the
# shifted polynomials are bounded by 1 on [0, lmax]), each bf16 store
# rounds with relative error <= 2^-8, and the f32 combine contracts at
# most the coefficient mass against the rounded buffers. 16x covers the
# coefficient-mass factor for every bank we ship (observed <= 9e-3 rel).
BF16_REL_BOUND = 16 * 2.0**-8

ORDERS = [5, 20, 80]
# Block 8, and block 32, where a block-row holds several stored tiles.
BLOCKS = pytest.mark.parametrize("block", [8, 32], ids=["block8", "block32"])


@pytest.fixture(scope="module")
def setting():
    g = graph.connected_sensor_graph(
        jax.random.PRNGKey(3), n=96, sigma=0.17, kappa=0.18)
    f = jax.random.normal(jax.random.PRNGKey(4), (g.n_vertices, 8))
    return g, f


def _filter(g, order):
    return GraphFilter.from_multipliers(
        [multipliers.heat(0.5), multipliers.tikhonov(1.0, 1)],
        order, graph=g)


def _rel_err(got, want):
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _bsr(filt, f, block, **opts):
    """``filt`` applied on ``bsr`` with the Block-CSR tiles of ``block``."""
    assert filt.prepare_backend("bsr", block_size=block).mat.block_size == block
    return np.asarray(filt.apply(f, backend="bsr", block_size=block, **opts))


@BLOCKS
@pytest.mark.parametrize("order", ORDERS)
def test_bf16_within_documented_bound(setting, order, block):
    g, f = setting
    filt = _filter(g, order)
    want = _bsr(filt, f, block)
    got = _bsr(filt, f, block, krylov_dtype="bfloat16")
    assert got.dtype == want.dtype == np.float32  # combine stays f32
    assert _rel_err(got, want) < BF16_REL_BOUND, (order, block)


@BLOCKS
def test_bf16_error_does_not_grow_with_order(setting, block):
    """|Tbar_k| <= 1 stability: M=80 is no worse than a few x M=5."""
    g, f = setting
    errs = {}
    for order in ORDERS:
        filt = _filter(g, order)
        want = _bsr(filt, f, block)
        got = _bsr(filt, f, block, krylov_dtype="bfloat16")
        errs[order] = _rel_err(got, want)
    assert errs[80] < 4.0 * max(errs[5], 1e-4), errs


@BLOCKS
@pytest.mark.parametrize("order", ORDERS)
def test_f32_krylov_is_bit_identical(setting, order, block):
    """The Krylov buffers are cast to ``krylov_dtype``; at f32 the casts
    are no-ops and the output must not change by a single bit."""
    g, f = setting
    filt = _filter(g, order)
    default = _bsr(filt, f, block)
    explicit = _bsr(filt, f, block, krylov_dtype="float32")
    assert default.tobytes() == explicit.tobytes(), (order, block)


@pytest.mark.parametrize("krylov_dtype", ["float32", "bfloat16"])
def test_order_one_bsr_matches_dense(setting, krylov_dtype):
    """Order 1 returns after the first step (``T_0``, ``T_1`` and the
    combine, no scan), so it matches ``dense`` to f32 in either mode."""
    g, f = setting
    filt = _filter(g, 1)
    want = np.asarray(filt.apply(f, backend="dense"))
    got = np.asarray(filt.apply(f, backend="bsr", krylov_dtype=krylov_dtype))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_gram_and_higher_order_paths_accept_krylov_dtype(setting):
    """gram routes **opts through the same backend — bf16 holds there
    too (degree-2M recurrence)."""
    g, f = setting
    filt = _filter(g, 20)
    want = np.asarray(filt.gram(f, backend="bsr"))
    got = np.asarray(filt.gram(f, backend="bsr", krylov_dtype="bfloat16"))
    assert _rel_err(got, want) < BF16_REL_BOUND
