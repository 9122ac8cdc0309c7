"""Program spans and compile counters (``repro.obs``), on the CPU."""

from __future__ import annotations

import glob
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import graph, multipliers
from repro.filters import GraphFilter
from repro.serve import AsyncGraphFilterEngine, SchedulerConfig

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def recording():
    obs.enable()
    try:
        yield
    finally:
        obs.disable()


@pytest.fixture(scope="module")
def small_filter():
    g = graph.connected_sensor_graph(jax.random.PRNGKey(1), n=96, sigma=0.17, kappa=0.18)
    filt = GraphFilter.from_multipliers(
        [multipliers.tikhonov(1.0, 1), multipliers.heat(0.5)], order=8, graph=g)
    sigs = np.random.default_rng(3).normal(size=(12, g.n_vertices)).astype(np.float32)
    return filt, sigs


def test_nesting_and_self_time(recording):
    before = obs.snapshot()
    with obs.span("t.outer", apply=1):
        time.sleep(0.02)
        with obs.span("t.inner"):
            time.sleep(0.03)
        with obs.span("t.inner"):
            time.sleep(0.01)
    d = obs.diff(obs.snapshot(), before)
    outer, inner = d["spans"]["t.outer"], d["spans"]["t.inner"]
    assert outer["n"] == 1 and inner["n"] == 2
    assert inner["self_s"] == pytest.approx(inner["s"])  # no children
    assert outer["self_s"] == pytest.approx(outer["s"] - inner["s"])
    assert 0.015 < outer["self_s"] < outer["s"]


def test_recording_off_keeps_nothing():
    obs.disable()
    assert not obs.enabled()
    before = obs.snapshot()
    with obs.span("t.off", apply=2):
        pass
    assert obs.span("t.a") is obs.span("t.b")  # the one shared no-op
    assert obs.diff(obs.snapshot(), before)["spans"] == {}


def test_fresh_jit_counts_one_trace_and_one_compile():
    def obs_probe(x):
        return jnp.sin(x) * 3.0

    f = jax.jit(obs_probe)
    x = jnp.ones((7,), jnp.float32)
    x.block_until_ready()
    before = obs.snapshot()
    f(x).block_until_ready()
    mid = obs.snapshot()
    f(x).block_until_ready()
    first, again = obs.diff(mid, before), obs.diff(obs.snapshot(), mid)
    assert first["compiles"]["trace"]["by_fun"].get("obs_probe") == 1
    assert first["compiles"]["compile"]["by_fun"].get("jit(obs_probe)") == 1
    assert first["compiles"]["compile"]["s"] > 0
    assert all(c["n"] == 0 for c in again["compiles"].values())


def test_compiles_inside_a_profiler_trace_are_marked_profiled(tmp_path):
    x = jnp.ones((5,), jnp.float32)
    before = obs.snapshot()
    jax.jit(lambda v: v * 5.0 - 1.0)(x).block_until_ready()
    outside = obs.snapshot()
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.jit(lambda v: v * 7.0 + 2.0)(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    first, traced = obs.diff(outside, before), obs.diff(obs.snapshot(), outside)
    assert first["compiles"]["compile"]["n"] == 1
    assert first["compiles"]["compile"]["profiled_n"] == 0
    assert traced["compiles"]["compile"]["n"] == traced["compiles"]["compile"]["profiled_n"] == 1
    assert traced["compiles"]["compile"]["profiled_s"] == traced["compiles"]["compile"]["s"] > 0
    ev = [e for e in traced["events"] if e["kind"] == "compile"]
    assert [e["profiled"] for e in ev] == [True]


def test_compile_events_name_the_open_span(recording):
    before = obs.snapshot()
    with obs.span("t.build"):
        jax.jit(lambda x: x + 41.0)(jnp.ones((3,), jnp.float32)).block_until_ready()
    ev = [e for e in obs.diff(obs.snapshot(), before)["events"] if e["kind"] == "compile"]
    assert ev and all(e["span"] == "t.build" for e in ev)
    assert all(e["start_ns"] <= e["t_ns"] for e in ev)


def _profiled(run, trace_dir) -> list[tuple[str, float, float, dict]]:
    """Run ``run()`` under a profiler trace; its ``repro.*`` events as
    (name without the prefix, start_ns, end_ns, args), in start order."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(trace_dir))
    try:
        run()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(Path(trace_dir) / "plugins" / "profile" / "*" / "*.xplane.pb"))[0]
    out = [(e.name[len("repro."):], e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
           for p in ProfileData.from_file(path).planes for ln in p.lines
           for e in ln.events if e.name.startswith("repro.")]
    return sorted(out, key=lambda e: e[1])


def test_profiler_trace_holds_program_spans(recording, tmp_path):
    def run():
        with obs.span("t.profiled", apply=3):
            with obs.span("t.child"):
                jnp.ones((4,)).block_until_ready()

    ev = _profiled(run, tmp_path)
    assert [(e[0], e[3]) for e in ev] == [("t.profiled", {"apply": 3}), ("t.child", {})]
    (_, s0, e0, _), (_, s1, e1, _) = ev
    assert s0 <= s1 <= e1 <= e0  # the child nests inside its parent


def test_engine_panel_spans_and_tickets_share_panel_ids(recording, small_filter, tmp_path):
    filt, sigs = small_filter
    eng = AsyncGraphFilterEngine(
        filt, backend="dense", config=SchedulerConfig(max_panel=4, min_bucket=4))
    tks = []

    def run():
        tks.extend(eng.submit(s) for s in sigs[:6])  # one full panel of 4, then 2
        eng.step()
        eng.drain()

    ev = _profiled(run, tmp_path)
    assert all(t.done for t in tks)
    by_panel: dict[int, list[str]] = {}
    for name, _, _, args in ev:
        if name.startswith("engine.") and "panel" in args:
            by_panel.setdefault(args["panel"], []).append(name)
    assert {t.panel for t in tks} == set(by_panel) and len(by_panel) == 2
    for names in by_panel.values():
        assert names == ["engine.pack", "engine.dispatch", "engine.fetch",
                         "engine.unpack", "engine.resolve"]
    assert [t.panel for t in tks] == [tks[0].panel] * 4 + [tks[4].panel] * 2
    assert all(t.queue_s >= 0.0 for t in tks)
    assert "engine.step" in {e[0] for e in ev}


def test_engine_tickets_carry_no_ids_with_recording_off(small_filter):
    obs.disable()
    filt, sigs = small_filter
    eng = AsyncGraphFilterEngine(
        filt, backend="dense", config=SchedulerConfig(max_panel=4, min_bucket=4))
    tks = [eng.submit(s) for s in sigs[:5]]
    eng.drain()
    assert all(t.done for t in tks)
    assert all(t.panel is None and t.queue_s is None for t in tks)


def test_engine_cache_misses_equal_panel_program_compiles(small_filter):
    """``engine.recompiles`` (the cache's misses) equals the XLA compiles
    the compile counter saw for the engine's panel programs."""
    filt, sigs = small_filter
    eng = AsyncGraphFilterEngine(
        filt, backend="bsr", config=SchedulerConfig(max_panel=8, min_bucket=4))
    filt.prepare_backend("bsr")
    before = obs.snapshot()
    for width in (8, 3, 5, 8, 1):  # buckets 8, 4, 8, 8, 4
        tks = [eng.submit(s) for s in sigs[:width]]
        eng.drain()
        assert all(t.done for t in tks)
    d = obs.diff(obs.snapshot(), before)
    panel = {f: n for f, n in d["compiles"]["compile"]["by_fun"].items()
             if f == "jit(panel_apply)"}
    assert eng.recompiles == 2
    assert sum(panel.values()) == eng.recompiles


def test_layout_record_is_always_on(small_filter):
    """With recording off, preparing ``bsr`` leaves its layout in the
    snapshot: block, block-rows, stored tiles and their bytes, beside the
    Laplacian's vertex count and nonzeros; ``diff`` keeps the later one."""
    filt, _ = small_filter
    assert not obs.enabled()
    before = obs.snapshot()
    mat = filt.prepare_backend("bsr", block_size=16).mat
    snap = obs.snapshot()
    lap = np.asarray(filt.graph.laplacian())
    assert snap["layout"] == {
        "backend": "bsr", "n": 96, "nnz": int(np.count_nonzero(lap)), "block": 16,
        "block_rows": 6, "stored_tiles": mat.n_tiles, "tile_bytes": mat.n_tiles * 16 * 16 * 4}
    assert obs.diff(snap, before)["layout"] == snap["layout"]


def test_kernel_trace_counts_read_the_counter():
    from repro.dynamic import delta

    b = 6
    args = (jnp.eye(b), jnp.zeros((b, b)), jnp.ones((4, b, 2)), jnp.ones((2, 4)), 2.0)
    before = delta.kernel_trace_counts().get("churn_correction", 0)
    delta.churn_correction(*args)[0].block_until_ready()
    after = delta.kernel_trace_counts().get("churn_correction", 0)
    delta.churn_correction(*args)[0].block_until_ready()
    assert after == before + 1  # a bucket of 6 is this test's own shape
    assert delta.kernel_trace_counts()["churn_correction"] == after


HALO_SPANS = r"""
import glob
import json
import os
import tempfile
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
from jax.profiler import ProfileData
from repro import obs
from repro.core import graph, multipliers
from repro.filters import GraphFilter

g = graph.connected_sensor_graph(jax.random.PRNGKey(4), n=160, sigma=0.12, kappa=0.14)
filt = GraphFilter.from_multipliers([multipliers.heat(0.5)], 8, graph=g)
f = jax.random.normal(jax.random.PRNGKey(5), (g.n_vertices, 4))
obs.enable()
filt.apply(f, backend="halo", n_parts=4).block_until_ready()
snap = obs.snapshot()
trace_dir = tempfile.mkdtemp()
jax.profiler.start_trace(trace_dir)
filt.apply(f, backend="halo", n_parts=4).block_until_ready()
jax.profiler.stop_trace()
path = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))[0]
ev = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats))
            for p in ProfileData.from_file(path).planes for ln in p.lines
            for e in ln.events if e.name.startswith("repro.halo."))
print(json.dumps({
    "devices": len(jax.devices()),
    "setup": sorted(snap["spans"]),
    "apply": [[e[2], e[0], e[1], e[3].get("apply")] for e in ev],
}))
"""


def test_halo_apply_spans_in_order_on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"))
    proc = subprocess.run([sys.executable, "-c", HALO_SPANS], capture_output=True,
                          text=True, env=env, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4
    assert {"filter.prepare", "halo.plan", "halo.context", "graph.spatial_order"} <= set(
        out["setup"])
    names = [a[0] for a in out["apply"]]
    assert names == ["repro.halo." + k for k in ("scatter", "dispatch", "gather")]
    ids = {a[3] for a in out["apply"]}
    assert len(ids) == 1 and None not in ids  # one apply id
    ends = [a[2] for a in out["apply"]]
    starts = [a[1] for a in out["apply"]]
    assert all(e <= s for e, s in zip(ends, starts[1:]))  # back to back, no overlap
