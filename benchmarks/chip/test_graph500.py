"""Tests of the Graph500 deployment's benchmark files, on the CPU at small
sizes: the generator against the configuration's counts, whole runs of
``graph500_s14.batch`` cut to scale 8, and the ``bsr.tile_bytes_ratio``
reader of the program's layout record.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip

Like ``test_bench.py``, not among the repository's tier-1 tests.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import opcount  # noqa: E402

CONF = json.loads((HERE / "configs" / "graph500_s14.json").read_text())
SMALL = {"config": {"scale": 8, "n_vertices": 256, "signal_batch": 8},
         "traffic": {"sample_applies": 2}}


def _generator():
    import harness

    return harness.load_module(HERE / "graphs" / "graph500.py")


def _undirected(i, j, n):
    """Distinct undirected edges (u < v) of the draws, self-loops dropped."""
    keep = i != j
    e = np.unique(np.minimum(i, j)[keep] * n + np.maximum(i, j)[keep])
    return e // n, e % n


def test_generator_reproduces_the_configuration_counts():
    """At scale 14 and seed 0, from the edge list alone (no dense build):
    the counts and the Anderson--Morley bound the configuration states."""
    n = CONF["n_vertices"]
    i, j = _generator().edges(CONF["scale"], CONF["edgefactor"], CONF["initiator"],
                              CONF["graph_seed"])
    assert len(i) == CONF["edgefactor"] * n
    u, v = _undirected(i, j, n)
    deg = np.bincount(u, minlength=n) + np.bincount(v, minlength=n)
    assert len(u) == CONF["graph_edges"] == 212_981
    assert deg.max() == CONF["graph_max_degree"] == 3_654
    assert (deg == 0).sum() == CONF["graph_isolated"] == 3_867
    assert np.percentile(deg, 99) == CONF["graph_p99_degree"]
    assert 2 * len(u) / n == CONF["graph_mean_degree"]
    assert (deg[u] + deg[v]).max() == CONF["lmax"]


def test_make_builds_the_edge_list_without_coordinates():
    conf = dict(CONF, scale=8, n_vertices=256)
    gen = _generator()
    coords, adj, lmax, nnz = gen.make(conf)
    u, v = _undirected(*gen.edges(8, conf["edgefactor"], conf["initiator"], 0), 256)
    want = np.zeros((256, 256), np.float32)
    want[u, v] = want[v, u] = 1.0
    assert coords is None
    np.testing.assert_array_equal(np.asarray(adj), want)
    deg = want.sum(axis=1)
    assert nnz == 2 * len(u) and lmax == (deg[u] + deg[v]).max()
    with pytest.raises(ValueError, match="not 2\\^scale"):
        gen.make(dict(conf, n_vertices=300))


def _run():
    import harness

    return harness.run_cell("graph500_s14.batch", 2**31 + 11, 1.0, False,
                            t_start=time.perf_counter(), require_chip=False, overrides=SMALL)


def test_sound_run_is_correct():
    res = _run()
    assert res["correct"] and res["compared"]["answers_missing"]["value"] == 0
    assert res["compared"]["rel_max_err"]["value"] < CONF["check"]["rel_max_err_limit"]
    assert set(res["metrics"]) == {"columns_per_s", "setup_s"}


def test_answer_altered_is_not_correct(monkeypatch):
    from repro.filters import backends

    apply = backends.BsrBackend.apply

    def altered(self, *a, **k):
        out = apply(self, *a, **k)
        return out.at[0, 0, 0].add(1.0)

    monkeypatch.setattr(backends.BsrBackend, "apply", altered)
    assert not _run()["correct"]


# -- bsr.tile_bytes_ratio ----------------------------------------------------


def _read_ratio():
    import harness

    return harness.read_metric("bsr.tile_bytes_ratio", {})


def test_tile_bytes_ratio_of_a_prepared_filter():
    """The layout record of the filter prepared last, over the bytes of its
    Laplacian compressed by row."""
    import harness

    conf = dict(CONF, scale=8, n_vertices=256)
    coords, adj, lmax, _ = harness.make_graph(conf)
    filt = harness.build_program(conf, {"coords": coords, "adj": adj, "lmax": lmax})
    mat = filt.prepare_backend("bsr").mat
    from repro import obs

    layout = obs.snapshot()["layout"]
    nnz = int(np.count_nonzero(np.asarray(filt.graph.laplacian())))
    assert layout == {"backend": "bsr", "n": 256, "nnz": nnz, "block": mat.block_size,
                      "block_rows": mat.n_block_rows, "stored_tiles": mat.n_tiles,
                      "tile_bytes": mat.n_tiles * mat.block_size**2 * 4}
    csr = nnz * (opcount.F32 + opcount.I32) + 257 * opcount.I32
    assert _read_ratio() == pytest.approx(layout["tile_bytes"] / csr, rel=1e-12)


def test_tile_bytes_ratio_gives_nothing_without_the_record(monkeypatch):
    """The parent's ``repro.obs`` has no layout record; no ``repro.obs``
    at all gives nothing too. Neither raises."""
    snap = {"t_ns": 0, "spans": {}, "compiles": {}, "events": []}
    monkeypatch.setitem(sys.modules, "repro.obs", SimpleNamespace(snapshot=lambda: snap))
    assert _read_ratio() is None
    monkeypatch.delitem(sys.modules, "repro.obs")
    assert _read_ratio() is None
