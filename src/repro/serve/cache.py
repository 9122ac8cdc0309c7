"""Compiled-program cache for the serving engine's panel lanes.

The cache's keys are the power-of-two shape buckets the scheduler packs
panels into (``repro.filters.bucket_size``), so a workload with wobbling
panel widths settles onto a logarithmic number of programs: every bucket
compiles exactly once (its cache *miss*), and steady-state traffic is
all *hits* — the recompile counter the load harness and the acceptance
tests read is simply ``misses``.

Cached programs are built with the panel input buffer *donated*
(``GraphFilter.panel_program(donate=True)`` / the solve-lane
``donate_argnums``, see ``launch.donation``): the engine packs a fresh
panel per batch and never reads it back, so at steady state a lane is
allocation-stable — cached program + recycled panel buffer, no per-batch
net device allocation.
"""

from __future__ import annotations

from typing import Any, Callable, Hashable

__all__ = ["CompiledPanelCache"]


class CompiledPanelCache:
    """Build-once dictionary of compiled panel programs with hit/miss
    counters.

    A "program" is whatever the builder returns — a ``jax.jit``-wrapped
    apply for traceable backends, a plain callable otherwise; the cache
    only guarantees ``build`` runs once per key. A miss builds the
    wrapper only; the trace and compile follow on its first call. Because
    every cached jitted program is fed exactly one input shape (its
    bucket), that is one compile per miss.
    """

    def __init__(self) -> None:
        self._programs: dict[Hashable, Any] = {}
        self.hits = 0
        self.misses = 0

    def get(self, key: Hashable, build: Callable[[], Any]) -> Any:
        """Return the program under ``key``, building it on first use."""
        try:
            prog = self._programs[key]
        except KeyError:
            prog = self._programs[key] = build()
            self.misses += 1
        else:
            self.hits += 1
        return prog

    def __len__(self) -> int:
        return len(self._programs)

    def __contains__(self, key: Hashable) -> bool:
        return key in self._programs

    def stats(self) -> dict[str, int]:
        """Counters snapshot: ``programs`` / ``hits`` / ``misses``."""
        return {
            "programs": len(self._programs),
            "hits": self.hits,
            "misses": self.misses,
        }
