"""Seconds of XLA compiles plus persistent-cache loads in set-up, from the
program's compile counter (``repro.obs``, always on).

A ``--trace 1`` run records a profile of the window alone, and the
counter marks each compile made while a profile was recorded. Set-up is
every other compile or cache load so far: the benchmark's field and
warm-up included (nothing compiles between the window and the readers).
A program without ``repro.obs`` gives nothing.
"""

import sys


def read(ctx):
    obs = sys.modules.get("repro.obs")
    if obs is None:
        return None
    c = obs.snapshot()["compiles"]
    return sum(c[k]["s"] - c[k]["profiled_s"] for k in ("compile", "cache_load"))
