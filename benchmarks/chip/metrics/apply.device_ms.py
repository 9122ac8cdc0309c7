"""Device milliseconds of one apply: the union of the device operations
inside the apply's program, averaged over the applies that started in the
traced window."""

import trace_reduce


def read(ctx):
    runs, secs = trace_reduce.module_runs(ctx["trace"], ctx["module"])
    if not runs or not secs:
        return None
    return 1e3 * secs / runs
