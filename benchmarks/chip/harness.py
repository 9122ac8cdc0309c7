"""One run of one benchmark cell, driven by the files that ``BENCHMARK.json``
names. ``run.py`` is the command line; tests call ``run_cell`` directly.

A cell is a configuration (``configs/<config>.json``: the deployment, its
field and bank, the precision it states and the limit of its check) under
a traffic mix (``traffic/<traffic>.json``: which loop drives which
backend, with its parameters). Per-layer metrics are read by
``metrics/<name>.py``, each a ``read(ctx)`` that returns a number or None.
Adding a cell, a mix or a metric adds files; none of this code changes.

Set-up (counted in ``setup_s``, from process start): the device check,
the field, the program's filter and backend state, and one warm-up of
every shape the window uses. The window then runs ``--seconds`` of the
cell's loop, traced with ``--trace 1``. After it closes: the device's
peak memory, the per-layer metrics, then the program's state is dropped
and the reference checks a seeded sample of what the window returned.
"""

from __future__ import annotations

import collections
import contextlib
import gc
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path

import jax
import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT / "src"))

import compare  # noqa: E402
import field  # noqa: E402
import gen  # noqa: E402
import opcount  # noqa: E402
import peaks  # noqa: E402
import reference  # noqa: E402
import trace_reduce  # noqa: E402

# Seed purposes: each draws its own stream from the run's --seed.
COLUMNS, SAMPLE, TRACE, POOL = 1, 2, 3, 4
WARM_INDEX = 2**30  # column batches of the warm-up, apart from the window's


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def load_cell(name: str) -> tuple[dict, dict, dict]:
    """(workload, configuration, traffic) of the cell ``name``."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = json.loads((ROOT / conf["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    cell = dict(cell, _bench=bench)
    return cell, config, traffic


def require_tpu(n_chips: int) -> dict:
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    if info["platform"] != "tpu":
        raise NoChip(f"no TPU: JAX reports {info}")
    if info["count"] < n_chips:
        raise NoChip(f"the cell needs {n_chips} chips, JAX reports {info}")
    return info


@contextlib.contextmanager
def span(name: str, on: bool):
    if on:
        with jax.profiler.TraceAnnotation("bench." + name):
            yield
    else:
        yield


def memory_peak_bytes(n: int) -> int:
    peaks_ = []
    for d in jax.devices()[:n]:
        stats = d.memory_stats() or {}
        peaks_.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks_)


# -- the program under test --------------------------------------------------


def build_program(config: dict, fld: dict):
    """The program's filter over the benchmark's field, as a user builds it."""
    from repro.core import multipliers
    from repro.core.graph import SensorGraph
    from repro.filters import GraphFilter

    g = SensorGraph(adjacency=fld["adj"], coords=fld["coords"])
    bank = multipliers.sgwt_filter_bank(fld["lmax"], config["n_scales"])
    return GraphFilter.from_multipliers(bank, order=config["order"], graph=g, lmax=fld["lmax"])


# -- loops ------------------------------------------------------------------


class ClosedLoop:
    """Back-to-back applies of fresh seeded (N, F) panels through
    ``GraphFilter.apply`` on the traffic's backend, each waited for before
    the next starts. Reports ``columns_per_s``: all columns over the whole
    window, which closes when the apply running at the deadline finishes."""

    def __init__(self, filt, config, traffic, seed):
        from repro.filters import backend_is_traceable

        self.n, self.f = config["n_vertices"], config["signal_batch"]
        self.opts = dict(traffic.get("opts", {}))
        backend = traffic["backend"]
        filt.prepare_backend(backend, **self.opts)

        def filter_apply(x):
            return filt.apply(x, backend=backend, **self.opts)

        self.apply = jax.jit(filter_apply) if backend_is_traceable(backend) else filter_apply
        self.key = gen.jax_key(seed, COLUMNS)
        self.sample_rng = gen.rng(seed, SAMPLE)
        self.n_sample = traffic["sample_applies"]
        self.module = "filter_apply"

    def warm(self):
        for i in range(2):
            x = gen.columns(self.key, WARM_INDEX + i, self.n, self.f)
            self.apply(x).block_until_ready()

    def window(self, seconds: float, traced: bool) -> dict:
        kept: dict[int, jax.Array] = {}
        i = 0
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while True:
            with span("generate", traced):
                x = gen.columns(self.key, i, self.n, self.f)
            with span("apply", traced):
                y = self.apply(x)
                y.block_until_ready()
            # Reservoir sample of the applies to check, drawn from the seed.
            if len(kept) < self.n_sample:
                kept[i] = y
            else:
                j = int(self.sample_rng.integers(0, i + 1))
                if j < self.n_sample:
                    kept.pop(sorted(kept)[j])
                    kept[i] = y
            i += 1
            if time.perf_counter() >= deadline:
                break
        t_end = time.perf_counter()
        self.kept = kept
        self.applies = i
        return {
            "attempted": i,
            "failed": 0,
            "e2e": {"columns_per_s": i * self.f / (t_end - t0)},
        }

    def answers(self):
        """(inputs (N, K), outputs (eta, N, K)) of the sampled applies."""
        idx = sorted(self.kept)
        xs = [np.asarray(gen.columns(self.key, i, self.n, self.f)) for i in idx]
        ys = [np.asarray(self.kept[i]) for i in idx]
        return np.concatenate(xs, axis=1), np.concatenate(ys, axis=2)

    def expected(self) -> int:
        return min(self.n_sample, self.applies) * self.f

    def counters(self) -> dict:
        return {}

    def close(self):
        self.apply = None
        self.kept = {i: np.asarray(y) for i, y in self.kept.items()}


class OpenLoop:
    """Open-loop single-column requests into ``AsyncGraphFilterEngine``:
    Poisson arrivals at the traffic's fixed rate from the copied
    ``make_trace``. Each request's latency runs from the moment it was due
    to the moment its result is back in the client's hands (after the
    engine's pump returns). Refused or unfinished requests fail."""

    def __init__(self, filt, config, traffic, seed):
        from repro.serve import AsyncGraphFilterEngine, SchedulerConfig

        self.n = config["n_vertices"]
        self.traffic = traffic
        sched = SchedulerConfig(**traffic["scheduler"])
        self.engine = AsyncGraphFilterEngine(filt, backend=traffic["backend"], config=sched,
                                             opts=traffic.get("opts", {}))
        self.buckets = sorted({min(sched.max_panel, sched.min_bucket * 2**k)
                               for k in range(16) if sched.min_bucket * 2**k <= 2 * sched.max_panel})
        self.seed = seed
        self.pool = gen.rng(seed, POOL).standard_normal(
            (traffic["n_signals"], self.n)).astype(np.float32)
        self.module = None

    def warm(self):
        """Compile and run one panel of every width bucket the window can use."""
        eng = self.engine
        for b in self.buckets:
            for _ in range(2):
                tks = [eng.submit(self.pool[j % len(self.pool)]) for j in range(b)]
                eng.drain()
                if not all(t.done for t in tks):
                    raise RuntimeError(f"warm-up panel of {b} left requests unanswered")
        self.base = dict(eng.stats(), pad_slots=eng.pad_slots, panel_slots=eng.panel_slots)

    def window(self, seconds: float, traced: bool) -> dict:
        from repro.serve import AdmissionError

        tr = self.traffic
        trace = gen.make_trace(
            tr["n_streams"], seconds, tr["rate_per_s"], rng=gen.rng(self.seed, TRACE),
            hot_frac=tr["hot_frac"], hot_mass=tr["hot_mass"], lane_mix=tuple(tr["lane_mix"]),
            n_tenants=tr["n_tenants"], n_signals=tr["n_signals"])
        r = trace.n_requests
        sample = set(gen.rng(self.seed, SAMPLE).choice(r, size=min(tr["sample_requests"], r),
                                                       replace=False).tolist())
        tenants = [f"t{k}" for k in range(trace.n_tenants)]
        eng = self.engine
        lat = np.full(r, np.nan)
        late = np.zeros(r)
        kept: dict[int, np.ndarray] = {}
        outstanding: collections.deque = collections.deque()
        refused = 0
        backlog_last = None
        t0 = time.perf_counter()
        due = t0 + trace.t_arrive
        close_by = t0 + seconds + 60.0
        i = 0
        while True:
            now = time.perf_counter()
            if i < r and due[i] <= now:
                with span("submit", traced):
                    while i < r and due[i] <= now:
                        try:
                            tk = eng.submit(self.pool[trace.signal[i]],
                                            tenant=tenants[trace.tenant[i]])
                            outstanding.append((i, tk))
                        except AdmissionError:
                            refused += 1
                        late[i] = time.perf_counter() - due[i]
                        i += 1
                if i >= r and backlog_last is None:
                    backlog_last = len(outstanding)
            with span("step", traced):
                ran = eng.step()
            now = time.perf_counter()
            while outstanding and outstanding[0][1].done:
                j, tk = outstanding.popleft()
                lat[j] = now - due[j]
                if j in sample:
                    kept[j] = np.array(tk.result)
            if i >= r and not outstanding:
                break
            if now > close_by:
                break
            if not ran:
                nxt = due[i] if i < r else close_by
                dl = eng.scheduler.oldest_deadline("apply")
                if dl is not None:
                    nxt = min(nxt, dl)
                if nxt > now:
                    with span("idle", traced):
                        time.sleep(nxt - now)
        t_close = time.perf_counter()
        missing = np.isnan(lat)
        lat[missing] = t_close - due[missing]  # refused or never answered: the wait so far
        self.kept = kept
        self.sample = sorted(sample)
        self.signal = trace.signal
        late_ms = late * 1e3
        fifth = max(r // 5, 1)
        out = {
            "attempted": r,
            "failed": int(missing.sum()),
            "e2e": {"p95_ms": float(np.percentile(lat, 95) * 1e3)},
            "p50_ms": float(np.percentile(lat, 50) * 1e3),
            "p95_first_fifth_ms": float(np.percentile(lat[:fifth], 95) * 1e3),
            "p95_last_fifth_ms": float(np.percentile(lat[-fifth:], 95) * 1e3),
            "backlog_at_last_arrival": backlog_last,
            "refused": refused,
            "lateness_p50_ms": float(np.percentile(late_ms, 50)),
            "lateness_p99_ms": float(np.percentile(late_ms, 99)),
            "lateness_max_ms": float(late_ms.max()),
        }
        log("generator", json.dumps({k: v for k, v in out.items() if k != "e2e"}))
        return out

    def answers(self):
        """(inputs (N, K), outputs (eta, N, K)) of the sampled requests that
        came back; a sampled request that did not is missing."""
        idx = [j for j in self.sample if j in self.kept]
        xs = np.stack([self.pool[self.signal[j]] for j in idx], axis=1)
        ys = np.stack([self.kept[j] for j in idx], axis=2)
        return xs, ys

    def expected(self) -> int:
        return len(self.sample)

    def counters(self) -> dict:
        eng = self.engine
        s = eng.stats()
        return {
            "served": s["served"] - self.base["served"],
            "panels": s["applies"] - self.base["applies"],
            "pad_slots": eng.pad_slots - self.base["pad_slots"],
            "panel_slots": eng.panel_slots - self.base["panel_slots"],
            "recompiles": s["recompiles"] - self.base["recompiles"],
        }

    def close(self):
        self.engine = None


LOOPS = {"closed": ClosedLoop, "open": OpenLoop}


# -- per-layer metrics --------------------------------------------------------


def read_metric(name: str, ctx: dict):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def cell_metrics(bench: dict, cell: str, kind: str) -> list[dict]:
    """The cell's end-to-end (``kind="end_to_end"``) or per-layer metrics."""
    return [m for m in bench[kind] if cell in m.get("workloads", [cell])]


# -- one run ------------------------------------------------------------------


def setup_cell(workload: str, seed: int, *, require_chip: bool = True,
               overrides: dict | None = None) -> dict:
    """Everything before the window: the device check, the field, the
    program's filter and backend state, and the warm-up."""
    cell, config, traffic = load_cell(workload)
    overrides = overrides or {}
    config.update(overrides.get("config", {}))
    traffic.update(overrides.get("traffic", {}))
    if require_chip:
        device = require_tpu(cell["chips"])
    else:
        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind, "count": len(jax.devices())}

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_default_matmul_precision", config["matmul_precision"])

    coords, adj, lmax, nnz_adj = field.make_field(
        config["field_seed"], config["n_vertices"], config["kappa"], config["sigma"])
    filt = build_program(config, {"coords": coords, "adj": adj, "lmax": lmax})
    loop = LOOPS[traffic["loop"]](filt, config, traffic, seed)
    loop.warm()
    # What set-up made lives to the end: keep the collector's full passes
    # in the window from walking it (a pause of about 100 ms otherwise).
    gc.collect()
    gc.freeze()
    return {"cell": cell, "config": config, "traffic": traffic, "device": device,
            "adj": adj, "lmax": lmax, "nnz_adj": nnz_adj, "loop": loop}


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *, t_start: float,
             require_chip: bool = True, overrides: dict | None = None,
             dump_trace: str | None = None) -> dict:
    """Run one cell once; returns the result object (the last stdout line)."""
    st = setup_cell(workload, seed, require_chip=require_chip, overrides=overrides)
    setup_s = time.perf_counter() - t_start
    cell, config, loop, device = st["cell"], st["config"], st["loop"], st["device"]
    adj, lmax, nnz_adj, n_chips = st["adj"], st["lmax"], st["nnz_adj"], cell["chips"]
    del st

    trace_dir = ROOT / ".bench_trace" / workload
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    with span("window", trace):
        res = loop.window(seconds, trace)
    if trace:
        jax.profiler.stop_trace()
    mem = memory_peak_bytes(n_chips) if require_chip else 0
    device["memory_peak_bytes"] = mem

    out_metrics = {}
    bench = cell["_bench"]
    if trace:
        planes = trace_reduce.load_xspace(str(trace_dir))
        if dump_trace:
            trace_reduce.dump_json(planes, dump_trace)
        shutil.rmtree(trace_dir, ignore_errors=True)
        red = trace_reduce.reduce(planes, n_devices=n_chips)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        n, f = config["n_vertices"], config["signal_batch"]
        eta = config["n_scales"] + 1
        nnz = nnz_adj + n  # L = D - A: the adjacency's entries and the diagonal
        ctx = {
            "trace": red,
            "counters": loop.counters(),
            "module": loop.module,
            "flops": opcount.union_apply_flops(n, nnz, f, config["order"], eta),
            "bytes": opcount.union_apply_bytes(n, nnz, f, config["order"], eta),
            "peak": peaks.peak_for(device["kind"]) if require_chip else None,
        }
        for m in cell_metrics(bench, workload, "per_layer"):
            v = read_metric(m["name"], ctx)
            if v is not None:
                out_metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        log("trace", json.dumps({k: red[k] for k in ("window_s", "busy_s", "collective_s",
                                                     "exposed_collective_s", "modules")}))
    else:
        e2e = dict(res["e2e"], setup_s=setup_s)
        for m in cell_metrics(bench, workload, "end_to_end"):
            out_metrics[m["name"]] = {"value": float(e2e[m["name"]]), "unit": m["unit"]}

    # The check: drop the program's state, then the reference.
    loop.close()
    gc.collect()
    xs, ys = loop.answers()
    expected = loop.expected()
    coeffs = field.cheb_coefficients(field.sgwt_bank(lmax, config["n_scales"]),
                                     config["order"], lmax)
    t_ref = time.perf_counter()
    ref = reference.reference_apply(adj, xs, coeffs, lmax, precision="highest")
    errs = [compare.rel_max_err(ys[:, :, k], ref[:, :, k]) for k in range(xs.shape[1])]
    err = max(errs) if errs else float("inf")
    v = compare.verdict(err, len(errs), expected, config["check"]["rel_max_err_limit"])
    log(f"reference seconds={time.perf_counter() - t_ref} columns={len(errs)}")

    result = {
        "correct": v["correct"],
        "attempted": res["attempted"],
        "failed": res["failed"],
        "metrics": out_metrics,
        "device": device,
    }
    if trace:
        result["breakdown"] = trace_reduce.breakdown(red)
    result["compared"] = v["checks"]
    for k, c in v["checks"].items():
        log(f"check {k} value={c['value']} limit={c['limit']}")
    return result
