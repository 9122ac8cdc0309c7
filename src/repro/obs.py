"""Program spans and compile counters, on the device trace's clock.

One module, three instruments:

* **Spans.** ``with obs.span("halo.gather", apply=7): ...`` marks one
  layer boundary. While recording is on (``enable()``), a span enters
  ``jax.profiler.TraceAnnotation("repro.<name>", **args)`` so it lands in
  the profiler's own trace beside the device's operations (start, end,
  nesting and args are read there), and adds its count, total and self
  seconds to a per-name aggregate that drops nothing. Self time is the
  span's duration less what its child spans (on the same thread) cover.
  Args known only inside the span go in by ``set`` on the context it
  yields. While recording is off, ``span`` returns one shared no-op
  context after a single flag test, and records nothing; callers that
  compute an id for a span's args test ``enabled()`` first.
* **Compile counter**, always on: listeners registered once through
  ``jax.monitoring`` count jaxpr traces, XLA compiles and persistent-cache
  loads, each with its ``fun_name``, its seconds, the innermost program
  span open on the thread at the time, and whether a ``jax.profiler``
  trace was being recorded (``profiled``): a compile inside a profile is
  one the profile's timeline holds. JAX reports a cache load as a
  ``backend_compile_duration`` event too (the compile span wraps the cache
  read), so a backend compile during which the cache reported a hit is
  kept as a ``cache_load``, never as both.

* **Layout record**, always on: the operand layout a backend prepared
  last, as plain numbers (``record_layout``): the ``bsr`` backend's
  block, block-rows, stored tiles and tile bytes, beside the vertex count
  and nonzeros of the Laplacian it tiled.

``snapshot()`` returns all of it as plain data and ``diff(after, before)``
what happened between two snapshots. Nothing is written to disk.
"""

from __future__ import annotations

import collections
import contextlib
import threading
import time

import jax

try:  # where ``jax.profiler.start_trace`` keeps its session
    from jax._src import profiler as _jax_profiler
except ImportError:  # pragma: no cover
    _jax_profiler = None

__all__ = ["span", "enable", "disable", "enabled", "snapshot", "diff", "compile_counts",
           "record_layout"]

#: Compile-counter event kinds.
KINDS = ("trace", "compile", "cache_load")

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

_KEEP_EVENTS = 4096

_on = False
_lock = threading.Lock()
_local = threading.local()

_agg: dict[str, list] = {}  # name -> [count, total_ns, self_ns]
# kind -> [n, s, by fun, n profiled, s profiled]
_counts = {k: [0, 0.0, collections.Counter(), 0, 0.0] for k in KINDS}
_events: collections.deque = collections.deque(maxlen=_KEEP_EVENTS)
_layout: dict | None = None


def _stack() -> list:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


class _Span:
    __slots__ = ("name", "args", "ann", "t0", "child_ns")

    def __init__(self, name: str, args: dict):
        self.name = name
        self.args = args

    def __enter__(self):
        self.ann = jax.profiler.TraceAnnotation("repro." + self.name, **self.args)
        self.ann.__enter__()
        _stack().append(self)
        self.child_ns = 0
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter_ns() - self.t0
        st = _stack()
        st.pop()
        parent = st[-1] if st else None
        if parent is not None:
            parent.child_ns += dur
        with _lock:
            rec = _agg.get(self.name)
            if rec is None:
                rec = _agg[self.name] = [0, 0, 0]
            rec[0] += 1
            rec[1] += dur
            rec[2] += dur - self.child_ns
        self.ann.__exit__(*exc)
        return False

    def set(self, **args) -> None:
        """Add args that are known only inside the span."""
        self.args.update(args)
        self.ann.set_metadata(**args)


class _NoSpan(contextlib.nullcontext):
    def __enter__(self):
        return self

    def set(self, **args) -> None:
        pass


_NOOP = _NoSpan()


def span(name: str, **args):
    """Context manager for one program span ``repro.<name>``; ``args``
    (ids such as ``apply=`` or ``panel=``) go to the profiler."""
    if not _on:
        return _NOOP
    return _Span(name, args)


def enable() -> None:
    """Start recording spans."""
    global _on
    _on = True


def disable() -> None:
    """Stop recording spans; what was recorded stays readable."""
    global _on
    _on = False


def enabled() -> bool:
    """Whether spans are being recorded."""
    return _on


# -- the compile counter -------------------------------------------------------


def _innermost() -> str | None:
    st = getattr(_local, "stack", None)
    return st[-1].name if st else None


def _profiling() -> bool:
    """Whether a ``jax.profiler`` trace is being recorded."""
    state = getattr(_jax_profiler, "_profile_state", None)
    return getattr(state, "profile_session", None) is not None


def _record(kind: str, fun: str, seconds: float, **extra) -> None:
    t = time.perf_counter_ns()
    profiled = _profiling()
    ev = {"kind": kind, "fun": fun, "s": seconds, "span": _innermost(), "profiled": profiled,
          "start_ns": t - int(seconds * 1e9), "t_ns": t, **extra}
    with _lock:
        c = _counts[kind]
        c[0] += 1
        c[1] += seconds
        c[2][fun] += 1
        if profiled:
            c[3] += 1
            c[4] += seconds
        _events.append(ev)


def _on_duration(event: str, duration_secs: float, **kw) -> None:
    if event == _TRACE_EVENT:
        _record("trace", str(kw.get("fun_name", "?")), duration_secs)
    elif event == _COMPILE_EVENT:
        hit = getattr(_local, "cache_hit", None)
        _local.cache_hit = None
        if hit is None:
            _record("compile", str(kw.get("fun_name", "?")), duration_secs)
        else:
            _record("cache_load", str(kw.get("fun_name", "?")), duration_secs, read_s=hit)
    elif event == _CACHE_READ_EVENT:
        _local.cache_hit = duration_secs


def _on_event(event: str, **kw) -> None:
    if event == _CACHE_HIT_EVENT and getattr(_local, "cache_hit", None) is None:
        _local.cache_hit = 0.0  # the read's seconds follow in their own event


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)


def compile_counts(kind: str = "trace") -> dict[str, int]:
    """Events of ``kind`` so far, by ``fun_name``."""
    with _lock:
        return dict(_counts[kind][2])


# -- the layout record ------------------------------------------------------


def record_layout(**fields) -> None:
    """Keep ``fields`` (plain numbers) as the layout prepared last."""
    global _layout
    with _lock:
        _layout = dict(fields)


# -- reading ---------------------------------------------------------------


def snapshot() -> dict:
    """Everything recorded so far, as plain data:

    ``spans``: name -> {"n", "s", "self_s"}; ``compiles``: kind ->
    {"n", "s", "by_fun", "profiled_n", "profiled_s"} (the last two: those
    while a ``jax.profiler`` trace was recorded); ``events``: the newest
    compile-counter events (kind, fun, s, span, profiled, start_ns, t_ns);
    ``layout``: the layout prepared last (``record_layout``), or None;
    ``t_ns``: when the snapshot was taken (``perf_counter_ns``).
    """
    with _lock:
        return {
            "t_ns": time.perf_counter_ns(),
            "spans": {k: {"n": n, "s": t * 1e-9, "self_s": s * 1e-9}
                      for k, (n, t, s) in _agg.items()},
            "compiles": {k: {"n": n, "s": s, "by_fun": dict(by),
                             "profiled_n": pn, "profiled_s": ps}
                         for k, (n, s, by, pn, ps) in _counts.items()},
            "events": list(_events),
            "layout": None if _layout is None else dict(_layout),
        }


def diff(after: dict, before: dict) -> dict:
    """What happened between two snapshots, in the snapshot's shape (the
    layout is the later snapshot's)."""
    t0 = before["t_ns"]
    spans = {}
    for k, a in after["spans"].items():
        b = before["spans"].get(k, {"n": 0, "s": 0.0, "self_s": 0.0})
        if a["n"] > b["n"]:
            spans[k] = {f: a[f] - b[f] for f in ("n", "s", "self_s")}
    compiles = {}
    for k, a in after["compiles"].items():
        b = before["compiles"][k]
        by = {f: n - b["by_fun"].get(f, 0) for f, n in a["by_fun"].items()}
        compiles[k] = {f: a[f] - b[f] for f in ("n", "s", "profiled_n", "profiled_s")}
        compiles[k]["by_fun"] = {f: n for f, n in by.items() if n}
    return {
        "t_ns": after["t_ns"],
        "spans": spans,
        "compiles": compiles,
        "events": [e for e in after["events"] if e["t_ns"] > t0],
        "layout": after["layout"],
    }
