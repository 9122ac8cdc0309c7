"""The comparison that decides ``correct``.

One number is compared: the relative max error of every compared answer
against the reference, max |got - ref| / max |ref|, taken over all the
answers compared in a run (each answer is all eta x N outputs of a
column). Its limit is set in the configuration file from two readings
(PERF.md section 2): the largest that sound runs of the program read over
a dozen seeds or more, and the smallest that the control reads.
"""

from __future__ import annotations

import numpy as np


def rel_max_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        return float("inf")
    if not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def verdict(err: float, n_compared: int, n_expected: int, limit: float) -> dict:
    """The compared numbers with their limits, and whether all hold."""
    checks = {
        "rel_max_err": {"value": err, "limit": limit},
        "answers_missing": {"value": n_expected - n_compared, "limit": 0},
    }
    ok = bool(err <= limit) and n_compared == n_expected and n_expected > 0
    return {"correct": ok, "checks": checks}
