"""Tests of the readers of the program's compile counter, and a pin of the
recorded trace's reduction, on the CPU.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip

Like ``test_bench.py``, not among the repository's tier-1 tests.
"""

from __future__ import annotations

import functools
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import trace_reduce  # noqa: E402

READERS = ("setup.compile_s", "window.compiles")


def read(name: str):
    import harness

    return harness.read_metric(name, {})


def test_recorded_tpu_trace_reduces_to_the_same_numbers():
    """The numbers the recorded trace of field16k.batch gave when it was
    recorded; a change to the reduction that moves them shows here."""
    planes = trace_reduce.load_json(str(HERE / "testdata" / "field16k_batch_trace.json.gz"))
    red = trace_reduce.reduce(planes, n_devices=1)
    exact = functools.partial(pytest.approx, rel=1e-12, abs=0)
    assert red["window_s"] == exact(0.6)
    assert red["busy_s"] == exact(0.592698337)
    assert red["collective_s"] == 0.0 and red["exposed_collective_s"] == 0.0
    assert red["modules"] == {
        "jit_columns(11379662363990634861)": (5, exact(0.00036618)),
        "jit_filter_apply(16573621680951434696)": (5, exact(0.482146681))}
    assert len(red["ops"]) == 30 and sum(red["ops"].values()) == exact(0.597369275)
    assert red["ops"]["cheb_step_pallas.9"] == exact(0.55971751)
    assert [g[0] for g in red["idle_gaps"]] == ["apply"] * 10
    assert [g[1] for g in red["idle_gaps"]] == [exact(v) for v in (
        0.001401487, 0.001376146, 0.001372705, 0.001321835, 0.001251856,
        0.000259382, 0.000139145, 9.9516e-05, 5.506e-05, 3.809e-06)]


def _fake_obs(compile_=(0, 0.0, 0, 0.0), cache_load=(0, 0.0, 0, 0.0), events=()):
    """A stand-in ``repro.obs`` whose snapshot holds the given counts:
    (n, s, profiled_n, profiled_s) per kind."""
    def kind(c):
        return {"n": c[0], "s": c[1], "by_fun": {}, "profiled_n": c[2], "profiled_s": c[3]}

    snap = {"t_ns": 0, "spans": {},
            "compiles": {"trace": kind((5, 0.5, 1, 0.1)), "compile": kind(compile_),
                         "cache_load": kind(cache_load)},
            "events": list(events)}
    return SimpleNamespace(snapshot=lambda: snap)


def test_readers_split_set_up_from_the_profiled_window(monkeypatch, capsys):
    ev = {"kind": "compile", "fun": "jit(filter_apply)", "s": 0.5, "span": None,
          "profiled": True, "start_ns": 0, "t_ns": 1}
    monkeypatch.setitem(sys.modules, "repro.obs", _fake_obs(
        compile_=(5, 6.5, 1, 0.5), cache_load=(4, 0.4, 2, 0.15),
        events=[dict(ev, profiled=False, fun="jit(columns)"), ev]))
    # Set-up: (6.5 - 0.5) compile + (0.4 - 0.15) cache load.
    assert read("setup.compile_s") == pytest.approx(6.25)
    assert read("window.compiles") == 3.0
    err = capsys.readouterr().err
    assert "jit(filter_apply)" in err and "jit(columns)" not in err  # only the window's


def test_window_compiles_reads_zero_when_nothing_compiled(monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.obs", _fake_obs(compile_=(9, 6.0, 0, 0.0)))
    assert read("window.compiles") == 0.0  # a reading, not None
    assert read("setup.compile_s") == pytest.approx(6.0)


def test_readers_give_nothing_over_a_program_without_obs(monkeypatch):
    """The parent of the change that added ``repro.obs``: the metric is
    left out of the line, and no reader raises."""
    monkeypatch.delitem(sys.modules, "repro.obs", raising=False)
    assert [read(name) for name in READERS] == [None, None]


def test_readers_over_the_real_counter(tmp_path):
    """A compile outside a profile adds to set-up; one inside adds to the
    window."""
    from repro import obs  # noqa: F401  (the program imports it)

    x = jnp.ones((6,), jnp.float32)
    s0, w0 = read("setup.compile_s"), read("window.compiles")
    jax.jit(lambda v: v * 3.0 + 1.5)(x).block_until_ready()
    s1, w1 = read("setup.compile_s"), read("window.compiles")
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.jit(lambda v: v * 4.0 - 2.5)(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    s2, w2 = read("setup.compile_s"), read("window.compiles")
    assert s1 > s0 and w1 == w0
    assert s2 == pytest.approx(s1) and w2 == w1 + 1
