"""Reduction of a profiler trace to the benchmark's device numbers.

A trace is read into plain ``Plane``/``Line``/``Event`` records (from the
profiler's ``.xplane.pb`` by ``load_xspace``, or from a recorded JSON by
``load_json``), and ``reduce`` turns them into:

* ``window_s``: the traced window, from the start of the benchmark's
  ``bench.window`` host span to its end;
* ``busy_s``: per device, the union of the intervals in which an
  operation ran (the ``XLA Ops`` line), clipped to the window; its mean
  over the devices used;
* ``ops``: device seconds by operation name, summed over devices, of the
  operations that hold no other (a ``while`` op spans its body's ops);
* ``modules``: per module name (the ``XLA Modules`` line), how many runs
  started in the window and their device seconds, from the union of the
  operations that ran inside them, averaged over the devices that ran it;
* ``collective_s`` / ``exposed_collective_s``: per device, the time in
  which a collective named in ``collectives`` ran, and the part of it
  during which no other operation ran on that device; means over
  devices;
* ``idle_gaps``: the longest gaps in device 0's busy union, each named
  by the benchmark host span (``bench.*``) that covers most of it.

Device planes are ``/device:TPU:<n>``. Times are in seconds.
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Line:
    name: str
    events: list


@dataclasses.dataclass
class Plane:
    name: str
    lines: list

    def line(self, name: str) -> list:
        for ln in self.lines:
            if ln.name == name:
                return ln.events
        return []


def load_xspace(log_dir: str) -> list:
    """Planes of the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    planes = []
    for pl in data.planes:
        keep_all = bool(DEVICE_PLANE.match(pl.name))
        lines = []
        for ln in pl.lines:
            evs = [Event(short_name(e.name), float(e.start_ns), float(e.duration_ns))
                   for e in ln.events if keep_all or e.name.startswith(SPAN_PREFIX)]
            if evs:
                lines.append(Line(ln.name, evs))
        planes.append(Plane(pl.name, lines))
    return planes


def short_name(name: str) -> str:
    """An HLO instruction's name without its text: on a TPU the ``XLA Ops``
    events are named ``%cheb_step_pallas.9 = f32[...] custom-call(...)``."""
    if name.startswith("%"):
        return name[1:].split(" = ", 1)[0]
    return name


def dump_json(planes: list, path: str) -> None:
    """Write planes as gzipped JSON (the recorded form tests read)."""
    doc = [{"name": p.name, "lines": [
        {"name": ln.name, "events": [[e.name, e.start_ns, e.dur_ns] for e in ln.events]}
        for ln in p.lines]} for p in planes]
    with gzip.open(path, "wt") as fh:
        json.dump(doc, fh)


def load_json(path: str) -> list:
    with gzip.open(path, "rt") as fh:
        doc = json.load(fh)
    return [Plane(p["name"], [Line(ln["name"], [Event(*e) for e in ln["events"]])
                              for ln in p["lines"]]) for p in doc]


# -- interval arithmetic (ns) --------------------------------------------


def merge(intervals) -> list:
    """Union of (start, end) intervals as a sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def measure(merged) -> float:
    return float(sum(e - s for s, e in merged))


def clip(merged, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in merged if e > lo and s < hi]


def intersect(a, b) -> list:
    """Intersection of two merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append([s, e])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def gaps(merged, lo: float, hi: float) -> list:
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append([t, s])
        t = max(t, e)
    if hi > t:
        out.append([t, hi])
    return out


# -- the reduction -------------------------------------------------------


def leaves(events: list) -> list:
    """The events that hold no other: on a TPU a ``while`` op spans the ops
    of its body on the same line."""
    evs = sorted(events, key=lambda e: (e.start_ns, -e.dur_ns))
    return [e for i, e in enumerate(evs)
            if i + 1 == len(evs) or evs[i + 1].end_ns > e.end_ns]


def host_spans(planes: list) -> list:
    return [e for p in planes if not DEVICE_PLANE.match(p.name)
            for ln in p.lines for e in ln.events if e.name.startswith(SPAN_PREFIX)]


def device_planes(planes: list, n_devices: int | None = None) -> list:
    devs = sorted((p for p in planes if DEVICE_PLANE.match(p.name)),
                  key=lambda p: int(p.name.rsplit(":", 1)[1]))
    return devs[:n_devices] if n_devices else devs


def _label(gap, spans) -> str:
    """The benchmark span, other than the window, that covers most of the
    gap; "window" where none does (the loop's own code between spans)."""
    best, name = 0.0, "none"
    for sp in spans:
        ov = min(gap[1], sp.end_ns) - max(gap[0], sp.start_ns)
        if sp.name == WINDOW_SPAN:
            if ov > 0 and best == 0.0:
                name = "window"
        elif ov > best:
            best, name = ov, sp.name[len(SPAN_PREFIX):]
    return name


def reduce(planes: list, *, n_devices: int | None = None,
           collectives: tuple = ("all-to-all",), top: int = 10) -> dict:
    spans = host_spans(planes)
    win = [s for s in spans if s.name == WINDOW_SPAN]
    devs = device_planes(planes, n_devices)
    if not devs:
        raise ValueError("trace holds no TPU device plane")
    if win:
        lo, hi = win[0].start_ns, win[0].end_ns
    else:
        evs = [e for p in devs for e in p.line(OPS_LINE)]
        lo, hi = min(e.start_ns for e in evs), max(e.end_ns for e in evs)
    busy, coll, exposed = [], [], []
    ops: dict[str, float] = {}
    modules: dict[str, list] = {}
    first_busy = None
    for p in devs:
        op_evs = leaves([e for e in p.line(OPS_LINE) if e.end_ns > lo and e.start_ns < hi])
        u = clip(merge((e.start_ns, e.end_ns) for e in op_evs), lo, hi)
        if first_busy is None:
            first_busy = u
        busy.append(measure(u))
        for e in op_evs:
            ops[e.name] = ops.get(e.name, 0.0) + e.dur_ns
        is_coll = [any(c in e.name for c in collectives) for e in op_evs]
        cu = clip(merge((e.start_ns, e.end_ns) for e, c in zip(op_evs, is_coll) if c), lo, hi)
        ou = clip(merge((e.start_ns, e.end_ns) for e, c in zip(op_evs, is_coll) if not c), lo, hi)
        coll.append(measure(cu))
        exposed.append(measure(cu) - measure(intersect(cu, ou)))
        for m in p.line(MODULES_LINE):
            if not lo <= m.start_ns < hi:
                continue
            rec = modules.setdefault(m.name, [0, 0.0, set()])
            rec[0] += 1
            rec[1] += measure(intersect(u, [[m.start_ns, m.end_ns]]))
            rec[2].add(p.name)
    n = len(devs)
    idle = sorted(gaps(first_busy, lo, hi), key=lambda g: g[0] - g[1])[:top]
    return {
        "window_s": (hi - lo) * 1e-9,
        "n_devices": n,
        "busy_s": sum(busy) / n * 1e-9,
        "busy_s_per_device": [b * 1e-9 for b in busy],
        "ops": {k: v * 1e-9 for k, v in ops.items()},
        "modules": {k: (c // len(d), s / len(d) * 1e-9) for k, (c, s, d) in modules.items()},
        "collective_s": sum(coll) / n * 1e-9,
        "exposed_collective_s": sum(exposed) / n * 1e-9,
        "idle_gaps": [[_label(g, spans), (g[1] - g[0]) * 1e-9] for g in idle],
    }


def module_runs(red: dict, substr: str) -> tuple[int, float]:
    """(runs, device seconds) of the modules whose name holds ``substr``."""
    runs, secs = 0, 0.0
    for name, (c, s) in red["modules"].items():
        if substr in name:
            runs += c
            secs += s
    return runs, secs


def breakdown(red: dict, top: int = 10) -> dict:
    ops = sorted(red["ops"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": red["idle_gaps"][:top]}
