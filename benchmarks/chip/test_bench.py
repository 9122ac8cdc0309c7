"""Tests of the on-chip benchmark itself, on the CPU at small sizes.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip

They are not among the repository's tier-1 tests (``testpaths`` is
``tests``). They check the yardstick: the contract of BENCHMARK.json, the
operation and byte count, the trace reduction on a small recorded TPU
trace, that the control and each fault a cell can have come out as not
correct, and that a run refuses any platform but TPU.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import compare  # noqa: E402
import field  # noqa: E402
import gen  # noqa: E402
import opcount  # noqa: E402
import reference  # noqa: E402
import trace_reduce  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SMALL_N = 256


def small(n: int = SMALL_N, **traffic) -> dict:
    kappa, sigma = field.field_radii(n)
    return {"config": {"n_vertices": n, "kappa": kappa, "sigma": sigma, "signal_batch": 8},
            "traffic": dict({"rate_per_s": 200, "sample_requests": 32, "n_signals": 64,
                             "sample_applies": 2,
                             "scheduler": {"max_panel": 16, "min_bucket": 8,
                                           "latency_budget_s": 0.05}}, **traffic)}


# -- BENCHMARK.json ---------------------------------------------------------


def test_benchmark_json_names_files_and_cells():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    per_layer = {m["name"]: m for m in BENCH["per_layer"]}
    for m in [*e2e.values(), *per_layer.values()]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file() and c["file"].startswith("benchmarks/chip/")
        conf = json.loads((ROOT / c["file"]).read_text())
        assert set(c["reduced"]) == set(conf["reduced"])
        assert (HERE / "graphs" / f"{conf['graph']}.py").is_file()
        if conf["graph"] == "sensor_field":
            assert np.allclose(field.field_radii(conf["n_vertices"]),
                               (conf["kappa"], conf["sigma"]))
    for m in per_layer.values():
        assert (HERE / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in e2e
    for w in BENCH["workloads"]:
        assert (HERE / "traffic" / f"{w['traffic']}.json").is_file()
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        mine = [m for m in e2e.values() if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in {m["name"] for m in mine} and len(mine) >= 2
        layer = [m for m in per_layer.values() if w["name"] in m.get("workloads", [])]
        assert layer and all(m["moves"] in {x["name"] for x in mine} for m in layer)


# -- graphs -----------------------------------------------------------------


def test_sensor_field_graph_is_the_field_bit_for_bit():
    """``graphs/sensor_field.py`` builds, at N=2,048, the adjacency, lmax and
    nnz of the field recipe as it was written before graphs were files."""
    import jax
    import jax.numpy as jnp

    import harness

    conf = json.loads((HERE / "configs" / "sensor_field_2k.json").read_text())
    n, kappa, sigma = conf["n_vertices"], conf["kappa"], conf["sigma"]

    @jax.jit
    def recipe(key):
        coords = jax.random.uniform(key, (n, 2), jnp.float32)
        d2 = jnp.sum((coords[:, None, :] - coords[None, :, :]) ** 2, axis=-1)
        w = jnp.where(d2 <= kappa**2, jnp.exp(-d2 / (2.0 * sigma**2)), 0.0)
        adj = jnp.where(jnp.eye(n, dtype=bool), 0.0, w)
        deg = adj.sum(axis=1)
        lmax = jnp.max(jnp.where(adj > 0, deg[:, None] + deg[None, :], 0.0))
        return coords, adj, lmax, jnp.count_nonzero(adj)

    want = recipe(jax.random.PRNGKey(conf["field_seed"]))
    coords, adj, lmax, nnz = harness.make_graph(conf)
    assert np.array_equal(np.asarray(coords), np.asarray(want[0]))
    assert np.array_equal(np.asarray(adj), np.asarray(want[1]))
    assert lmax == float(want[2]) and nnz == int(want[3]) == 2 * conf["field_edges"]


RING_GRAPH = """
import jax, jax.numpy as jnp
import field

def make(config):
    n = config["n_vertices"]
    i = jnp.arange(n)
    hop = jnp.abs(i[:, None] - i[None, :])
    hop = jnp.minimum(hop, n - hop)
    w = jax.random.uniform(jax.random.PRNGKey(config["field_seed"]), (n, n), jnp.float32)
    adj = jnp.where((hop > 0) & (hop <= 3), 0.5 + 0.5 * (w + w.T) / 2, 0.0)
    return None, adj, float(field.anderson_morley(adj)), int(jnp.count_nonzero(adj))
"""


def test_a_new_graph_is_added_as_a_file(tmp_path, monkeypatch):
    """A generator without coordinates, written as one file to a
    ``graphs/`` directory, drives a correct closed-loop run; no file of the
    harness changes."""
    import harness

    (tmp_path / "graphs").mkdir()
    (tmp_path / "graphs" / "ring.py").write_text(RING_GRAPH)
    monkeypatch.setattr(harness, "GRAPHS", tmp_path / "graphs")
    conf = dict(json.loads((HERE / "configs" / "sensor_field_16k.json").read_text()),
                name="ring_256", graph="ring")
    mix = json.loads((HERE / "traffic" / "batch.json").read_text())
    cell = {"name": "ring.batch", "config": "ring_256", "traffic": "batch", "chips": 1,
            "_bench": BENCH}
    monkeypatch.setattr(harness, "load_cell", lambda _name: (cell, conf, mix))
    built = []
    build_program = harness.build_program
    monkeypatch.setattr(harness, "build_program",
                        lambda config, fld: built.append(fld) or build_program(config, fld))
    res = harness.run_cell("ring.batch", 2**31 + 3, 1.0, False, t_start=time.perf_counter(),
                           require_chip=False,
                           overrides={"config": {"n_vertices": SMALL_N, "signal_batch": 8},
                                      "traffic": {"sample_applies": 2}})
    assert res["correct"] and res["compared"]["answers_missing"]["value"] == 0
    assert res["compared"]["rel_max_err"]["value"] < 1e-5
    (fld,) = built
    assert fld["coords"] is None and fld["adj"].shape == (SMALL_N, SMALL_N)
    assert int(np.count_nonzero(np.asarray(fld["adj"]))) == 6 * SMALL_N


def test_configuration_without_graph_is_refused():
    import harness

    conf = json.loads((HERE / "configs" / "sensor_field_2k.json").read_text())
    del conf["graph"]
    with pytest.raises(ValueError, match="'sensor_field_2k' names no graph"):
        harness.make_graph(conf)


# -- operations and bytes ---------------------------------------------------


def test_opcount_by_hand():
    # N=4, nnz=10, F=2, M=3, eta=2
    flops = opcount.union_apply_flops(4, 10, 2, 3, 2)
    assert flops == 3 * 2 * 10 * 2 + (2 * 4 * 4 * 2 + 2 * 4 * 2) + 2 * 2 * 4 * 4 * 2
    nbytes = opcount.union_apply_bytes(4, 10, 2, 3, 2)
    assert nbytes == 3 * (10 * 8 + 5 * 4 + 3 * 4 * 2 * 4) + 4 * 2 * 4 + 2 * 4 * 2 * 4
    peak = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    t, bound = opcount.least_seconds(flops, nbytes, peak)
    assert bound == "memory" and t == nbytes / 1e9


def test_opcount_at_the_cell_size_is_memory_bound():
    # N=16,384, mean degree 13.68: about 0.6 GB and 1.8 GFLOP per apply.
    n, nnz = 16384, 2 * 112073 + 16384
    b = opcount.union_apply_bytes(n, nnz, 128, 20, 5)
    f = opcount.union_apply_flops(n, nnz, 128, 20, 5)
    assert 5.5e8 < b < 6.5e8 and 1.6e9 < f < 2.0e9
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert opcount.least_seconds(f, b, peak)[1] == "memory"


# -- trace reduction --------------------------------------------------------


def _plane(name, ops=(), modules=(), spans=()):
    E = trace_reduce.Event
    lines = [trace_reduce.Line("XLA Ops", [E(*o) for o in ops]),
             trace_reduce.Line("XLA Modules", [E(*m) for m in modules])]
    if spans:
        lines = [trace_reduce.Line("python", [E(*s) for s in spans])]
    return trace_reduce.Plane(name, lines)


def test_reduce_busy_idle_and_exposed_collective():
    host = _plane("/host:CPU", spans=[("bench.window", 0, 100), ("bench.apply", 0, 60),
                                      ("bench.step", 60, 40)])
    d0 = _plane("/device:TPU:0",
                ops=[("fusion", 10, 20), ("all-to-all.1", 25, 15), ("while.1", 70, 10),
                     ("fusion", 70, 10)],
                modules=[("jit_filter_apply(1)", 5, 40), ("jit_filter_apply(1)", 65, 20)])
    d1 = _plane("/device:TPU:1", ops=[("all-to-all.1", 0, 50)])
    red = trace_reduce.reduce([host, d0, d1])
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["busy_s_per_device"] == pytest.approx([40e-9, 50e-9])
    assert red["busy_s"] == pytest.approx(45e-9)
    # device 0: all-to-all 25..40 overlaps compute 25..30 -> 10 exposed; device 1: 50.
    assert red["collective_s"] == pytest.approx((15e-9 + 50e-9) / 2)
    assert red["exposed_collective_s"] == pytest.approx((10e-9 + 50e-9) / 2)
    # The while op spans its body's fusion and is not counted twice.
    assert red["ops"]["fusion"] == pytest.approx(30e-9) and "while.1" not in red["ops"]
    # Only device 0 ran the module: 30 + 10 of busy time inside its two runs.
    assert trace_reduce.module_runs(red, "filter_apply") == (2, pytest.approx(40e-9))
    # Device 0's gaps, longest first, named by the host span covering most of each.
    assert red["idle_gaps"] == [["apply", pytest.approx(30e-9)], ["step", pytest.approx(20e-9)],
                                ["apply", pytest.approx(10e-9)]]


def test_reduce_recorded_tpu_trace():
    """A short traced run of field16k.batch recorded on a TPU v5 lite: the
    reduction finds the applies, their kernels and a small idle share."""
    planes = trace_reduce.load_json(str(HERE / "testdata" / "field16k_batch_trace.json.gz"))
    red = trace_reduce.reduce(planes, n_devices=1)
    runs, secs = trace_reduce.module_runs(red, "filter_apply")
    assert runs >= 2
    per_apply = secs / runs
    assert 0.05 < per_apply < 0.5
    assert 0.0 < red["busy_s"] <= red["window_s"]
    assert 1.0 - red["busy_s"] / red["window_s"] < 0.2
    top = trace_reduce.breakdown(red)["device_ops"]
    assert top and top[0][1] > 0.5 * red["busy_s"]


# -- the reference and its control ------------------------------------------


@pytest.fixture(scope="module")
def small_field():
    n = SMALL_N
    kappa, sigma = field.field_radii(n)
    coords, adj, lmax, _ = field.make_field(0, n, kappa, sigma)
    coeffs = field.cheb_coefficients(field.sgwt_bank(lmax, 4), 20, lmax)
    f = np.asarray(gen.columns(gen.jax_key(2**31 + 7, 1), 0, n, 16))
    return adj, lmax, coeffs, f


def test_reference_matches_an_eigendecomposition(small_field):
    adj, lmax, coeffs, f = small_field
    lap = np.asarray(reference.laplacian(adj), np.float64)
    lam, u = np.linalg.eigh(lap)
    x = 2.0 * lam / lmax - 1.0
    t = np.cos(np.arange(coeffs.shape[1])[:, None] * np.arccos(np.clip(x, -1, 1)))
    resp = coeffs @ t - 0.5 * coeffs[:, :1]  # (eta, N): sum_k c_k T_k(x), c_0 halved
    want = np.einsum("vi,ji,iw->jvw", u, resp, u.T @ f.astype(np.float64))
    got = reference.reference_apply(adj, f, coeffs, lmax)
    assert compare.rel_max_err(got, want) < 1e-5


def test_control_fails_the_limit(small_field):
    """The reference one precision lower (three bf16 passes) reads above
    the cells' limit; the reference itself reads far below it."""
    adj, lmax, coeffs, f = small_field
    ref = reference.reference_apply(adj, f, coeffs, lmax)
    ctl = reference.reference_apply(adj, f, coeffs, lmax, precision="bf16x3")
    limits = {json.loads((ROOT / c["file"]).read_text())["check"]["rel_max_err_limit"]
              for c in BENCH["configs"]}
    err = max(compare.rel_max_err(ctl[:, :, k], ref[:, :, k]) for k in range(f.shape[1]))
    assert err > max(limits)


# -- whole runs with the timed path broken underneath ------------------------


def adhoc_cell(name: str, config: str, traffic: str, chips: int):
    """A cell built from a configuration file and a traffic file, whether
    or not BENCHMARK.json lists it."""
    conf = json.loads((HERE / "configs" / f"{config}.json").read_text())
    mix = json.loads((HERE / "traffic" / f"{traffic}.json").read_text())
    cell = {"name": name, "config": config, "traffic": traffic, "chips": chips, "_bench": BENCH}
    return lambda _name: (cell, conf, mix)


def _run(cell, monkeypatch=None, **traffic):
    import harness

    if cell == "field2k.serve":
        monkeypatch.setattr(harness, "load_cell",
                            adhoc_cell(cell, "sensor_field_2k", "serve", 1))
    return harness.run_cell(cell, 2**31 + 99, 1.0, False, t_start=time.perf_counter(),
                            require_chip=False, overrides=small(**traffic))


def test_sound_batch_run_is_correct():
    res = _run("field16k.batch")
    assert res["correct"] and res["compared"]["answers_missing"]["value"] == 0
    assert list(res)[-1] == "compared"
    assert set(res["metrics"]) == {"columns_per_s", "setup_s"}


def test_batch_answer_altered_is_not_correct(monkeypatch):
    from repro.filters import backends

    apply = backends.BsrBackend.apply

    def altered(self, *a, **k):
        out = apply(self, *a, **k)
        return out.at[0, 0, 0].add(1.0)

    monkeypatch.setattr(backends.BsrBackend, "apply", altered)
    assert not _run("field16k.batch")["correct"]


def test_serve_sound_and_faults(monkeypatch):
    from repro.serve import async_engine

    res = _run("field2k.serve", monkeypatch)
    assert res["correct"] and res["failed"] == 0
    assert "setup_s" in res["metrics"]

    run_apply = async_engine.AsyncGraphFilterEngine._run_apply

    def swapped(self, batch):  # answers handed to the wrong requests
        out = run_apply(self, batch)
        return out[::-1]

    monkeypatch.setattr(async_engine.AsyncGraphFilterEngine, "_run_apply", swapped)
    assert not _run("field2k.serve", monkeypatch)["correct"]

    def half(self, batch):  # half of each panel's answers left out
        out = run_apply(self, batch)
        return out[: (len(out) + 1) // 2] + [np.zeros_like(o) for o in out[(len(out) + 1) // 2:]]

    monkeypatch.setattr(async_engine.AsyncGraphFilterEngine, "_run_apply", half)
    assert not _run("field2k.serve", monkeypatch)["correct"]


HALO_SCRIPT = r"""
import json, sys, time
sys.path.insert(0, {here!r})
import jax, jax.numpy as jnp
import harness, test_bench
harness.load_cell = test_bench.adhoc_cell("field16k.halo4", "sensor_field_16k", "halo4", 4)
if {broken}:
    jax.lax.all_to_all = lambda x, *a, **k: jnp.zeros_like(x)  # the exchange left out
res = harness.run_cell("field16k.halo4", 2**31 + 5, 1.0, False, t_start=time.perf_counter(),
                       require_chip=False, overrides=test_bench.small())
print(json.dumps({{"correct": res["correct"], "count": res["device"]["count"]}}))
"""


@pytest.mark.parametrize("broken", [False, True], ids=["sound", "exchange_left_out"])
def test_halo_on_four_devices(broken):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    proc = subprocess.run([sys.executable, "-c", HALO_SCRIPT.format(here=str(HERE), broken=broken)],
                          env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["count"] == 4
    assert out["correct"] is (not broken)


# -- the device check --------------------------------------------------------


def test_refuses_any_platform_but_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(HERE / "run.py"), "--workload", "field16k.batch",
                           "--seed", "1", "--seconds", "1"], env=env, capture_output=True,
                          text=True, timeout=300, cwd=str(ROOT))
    assert proc.returncode != 0
    assert "{" not in proc.stdout
