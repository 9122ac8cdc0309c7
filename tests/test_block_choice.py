"""The Block-CSR block size the ``bsr`` backend chooses from the
Laplacian's tile occupancy (``kernels/autotune.py``), on the CPU."""

from __future__ import annotations

import glob
import math
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import graph, multipliers
from repro.filters import GraphFilter, get_backend
from repro.kernels import autotune, ref


def _field(seed: int, n: int, degree: float = 14.0) -> graph.SensorGraph:
    """A seeded sensor field of about ``degree`` neighbours a vertex."""
    kappa = math.sqrt(degree / (math.pi * n))
    return graph.random_sensor_graph(jax.random.PRNGKey(seed), n=n, sigma=kappa, kappa=kappa)


def _chooser_order(g: graph.SensorGraph) -> np.ndarray:
    """The vertex order ``BsrBackend.prepare`` builds every tiling on."""
    parts = 1 << (-(-g.n_vertices // 8) - 1).bit_length()
    return graph.spatial_partition_order(np.asarray(g.coords), parts)


def _filter(g: graph.SensorGraph) -> GraphFilter:
    lmax = float(g.lmax_bound())
    return GraphFilter.from_multipliers(
        multipliers.sgwt_filter_bank(lmax, 2), order=12, graph=g, lmax=lmax)


def _block_ell_spans(run, trace_dir) -> list[dict]:
    """Args of the ``repro.bsr.block_ell`` spans ``run()`` records."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(trace_dir))
    try:
        run()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(Path(trace_dir) / "plugins" / "profile" / "*" / "*.xplane.pb"))[0]
    return [dict(e.stats) for p in ProfileData.from_file(path).planes for ln in p.lines
            for e in ln.events if e.name == "repro.bsr.block_ell"]


@pytest.fixture
def recording():
    obs.enable()
    try:
        yield
    finally:
        obs.disable()


@pytest.mark.parametrize("seed,n", [(0, 512), (1, 700), (2, 300), (3, 1000)])
def test_pooled_occupancy_matches_bsr_from_dense(seed, n):
    """Pooling the block-8 mask gives, at every candidate, the tiling that
    ``bsr_from_dense`` builds there from the matrix itself (N padded)."""
    g = _field(seed, n)
    perm = _chooser_order(g)
    lap = np.asarray(g.laplacian(), np.float64)[np.ix_(perm, perm)]
    fine = ref.tile_mask(lap, 8)
    for b in autotune.BLOCK_CANDIDATES:
        pooled = ref.tile_mask(fine, b // 8)
        np.testing.assert_array_equal(pooled, ref.tile_mask(lap, b))
        mat = ref.bsr_from_dense(lap, b)
        assert mat.n_tiles == int(ref.stored_tiles(pooled).sum()), b
        assert mat.n_block_rows == len(pooled)
        np.testing.assert_array_equal(np.asarray(ref.bsr_to_dense(mat))[:n, :n],
                                      lap.astype(np.float32))


def _band(nb: int) -> np.ndarray:
    """Tridiagonal tile mask: every coarser tiling keeps three tiles a row."""
    i = np.arange(nb)
    return (abs(i[:, None] - i[None, :]) <= 1)


def _scatter(nb: int) -> np.ndarray:
    """The diagonal and one far tile a row: coarser tilings pool only the
    diagonal, so their tiles a row grow with the block, and the stored
    tiles (4,092, 3,068, 2,556, 2,292, 2,156, 2,076 at blocks 8 ... 256
    for ``nb`` 2,048) fall less than each step's cost grows beyond 32."""
    mask = np.eye(nb, dtype=bool)
    mask[np.arange(nb), (37 * np.arange(nb) + nb // 2) % nb] = True
    return mask


@pytest.mark.parametrize("mask,setup_priced,want", [
    (_band(512), True, 128),  # 256 saves 2 us an order, and costs 0.25 s more set-up
    (_band(512), False, 256),  # the least predicted time an order
    (_scatter(2048), True, 32),
])
def test_chooser_on_hand_built_masks(monkeypatch, mask, setup_priced, want):
    if not setup_priced:
        monkeypatch.setattr(autotune, "SETUP_S_PER_TILE_BYTE", 0.0)
    choice = autotune.choose_block_size(mask, 20)
    assert choice.block_size == want
    assert choice.order_s <= choice.base_order_s


def test_chooser_weighs_no_block_beyond_the_padded_field():
    fine = np.eye(12, dtype=bool)  # N = 96 at block 8
    choice = autotune.choose_block_size(fine, 20)
    assert choice.block_size <= 96


def test_explicit_block_size_is_honoured(recording, tmp_path):
    filt = _filter(_field(5, 300))
    be = get_backend("bsr")
    states = []
    (args,) = _block_ell_spans(lambda: states.append(be.prepare(filt, block_size=16)), tmp_path)
    assert states[0].mat.block_size == 16
    assert args["chosen"] == "user" and args["block_size"] == 16
    assert args["slots"] == states[0].mat.n_tiles


def test_block_ell_span_carries_the_choice(recording, tmp_path):
    filt = _filter(_field(6, 1024))
    states = []
    (args,) = _block_ell_spans(lambda: states.append(get_backend("bsr").prepare(filt)), tmp_path)
    mat = states[0].mat
    nonzero = int(np.any(np.asarray(mat.blocks) != 0, axis=(1, 2)).sum())
    assert args["chosen"] == "model"
    assert args["block_size"] == mat.block_size in autotune.BLOCK_CANDIDATES
    assert args["slots"] == mat.n_tiles and args["tiles"] == nonzero
    assert args["fill"] == pytest.approx(nonzero / mat.n_tiles)
    assert 0.0 < args["order_ms"] <= args["base_order_ms"]


def test_bsr_at_the_chosen_block_matches_dense():
    g = _field(7, 600)
    filt = _filter(g)
    assert filt.prepare_backend("bsr").mat.block_size > 8
    f = np.random.default_rng(7).normal(size=(600, 8)).astype(np.float32)
    got = filt.apply(f, backend="bsr", interpret=True)
    want = filt.apply(f, backend="dense")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
