"""Precision tests for the bf16 Krylov-buffer mode (DESIGN.md Sec. 6.3).

Pins three contracts of ``krylov_dtype``:

* ``krylov_dtype="bfloat16"`` stays within the documented error bound
  ``||bf16 - f32||_inf <= 16 * 2^-8 * ||f32||_inf`` across orders
  M in {5, 20, 80}, on BOTH the fused union kernel and the stepwise
  chain — and the error does not grow with M (the shifted recurrence
  keeps ``|Tbar_k| <= 1``, so rounding does not compound);
* the default f32 path is bit-identical to the pre-refactor behavior:
  passing ``krylov_dtype="float32"`` (or nothing) changes no bits, the
  added casts are no-ops;
* halving the Krylov term is visible to the autotuner: bf16 admits
  fused shapes whose f32 working set busts the VMEM budget.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import graph, multipliers
from repro.filters import GraphFilter
from repro.kernels import autotune

# DESIGN.md Sec. 6.3: every stored T_k has |entries| <= ||f||_inf (the
# shifted polynomials are bounded by 1 on [0, lmax]), each bf16 store
# rounds with relative error <= 2^-8, and the f32 combine contracts at
# most the coefficient mass against the rounded buffers. 16x covers the
# coefficient-mass factor for every bank we ship (observed <= 9e-3 rel).
BF16_REL_BOUND = 16 * 2.0**-8

ORDERS = [5, 20, 80]


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


@pytest.mark.parametrize("fuse", [True, False], ids=["fused", "stepwise"])
@pytest.mark.parametrize("order", ORDERS)
def test_bf16_within_documented_bound(setting, order, fuse):
    g, f = setting
    filt = _filter(g, order)
    want = np.asarray(filt.apply(f, backend="bsr", fuse=fuse))
    got = np.asarray(filt.apply(
        f, backend="bsr", fuse=fuse, krylov_dtype="bfloat16"))
    assert got.dtype == want.dtype == np.float32  # combine stays f32
    assert _rel_err(got, want) < BF16_REL_BOUND, (order, fuse)


@pytest.mark.parametrize("fuse", [True, False], ids=["fused", "stepwise"])
def test_bf16_error_does_not_grow_with_order(setting, fuse):
    """|Tbar_k| <= 1 stability: M=80 is no worse than a few x M=5."""
    g, f = setting
    errs = {}
    for order in ORDERS:
        filt = _filter(g, order)
        want = np.asarray(filt.apply(f, backend="bsr", fuse=fuse))
        got = np.asarray(filt.apply(
            f, backend="bsr", fuse=fuse, krylov_dtype="bfloat16"))
        errs[order] = _rel_err(got, want)
    assert errs[80] < 4.0 * max(errs[5], 1e-4), errs


@pytest.mark.parametrize("fuse", [True, False], ids=["fused", "stepwise"])
@pytest.mark.parametrize("order", ORDERS)
def test_f32_krylov_is_bit_identical(setting, order, fuse):
    """The refactor added casts on the Krylov buffers; at f32 they are
    no-ops and the default output must not change by a single bit."""
    g, f = setting
    filt = _filter(g, order)
    default = np.asarray(filt.apply(f, backend="bsr", fuse=fuse))
    explicit = np.asarray(filt.apply(
        f, backend="bsr", fuse=fuse, krylov_dtype="float32"))
    assert default.tobytes() == explicit.tobytes(), (order, fuse)


def test_gram_and_higher_order_paths_accept_krylov_dtype(setting):
    """gram routes **opts through the same backend — bf16 holds there
    too (degree-2M recurrence)."""
    g, f = setting
    filt = _filter(g, 20)
    want = np.asarray(filt.gram(f, backend="bsr"))
    got = np.asarray(filt.gram(f, backend="bsr", krylov_dtype="bfloat16"))
    assert _rel_err(got, want) < BF16_REL_BOUND


# ------------------------------------------------ autotune threshold ---


def test_bf16_halves_krylov_vmem_term():
    args = dict(n=4096, f_tile=128, eta=3, n_tiles=256, block=128)
    f32 = autotune.union_vmem_bytes(*args.values())
    bf16 = autotune.union_vmem_bytes(
        *args.values(), krylov_dtype=jnp.bfloat16)
    krylov_f32 = 2 * args["n"] * args["f_tile"] * 4
    assert f32 - bf16 == krylov_f32 // 2


def test_bf16_raises_fuse_threshold():
    """A budget chosen between the bf16 and f32 working sets: f32 falls
    back to stepwise, bf16 fuses at the same shape."""
    shape = dict(n=4096, f=128, eta=3, n_tiles=256, block=128)
    f32_bytes = autotune.union_vmem_bytes(
        shape["n"], 128, shape["eta"], shape["n_tiles"], shape["block"])
    bf16_bytes = autotune.union_vmem_bytes(
        shape["n"], 128, shape["eta"], shape["n_tiles"], shape["block"],
        krylov_dtype=jnp.bfloat16)
    budget = (f32_bytes + bf16_bytes) // 2
    t_f32 = autotune.select_tiling(*shape.values(), vmem_budget=budget)
    t_bf16 = autotune.select_tiling(
        *shape.values(), vmem_budget=budget, krylov_dtype=jnp.bfloat16)
    assert t_bf16.fuse
    assert not t_f32.fuse or t_f32.f_tile < t_bf16.f_tile
