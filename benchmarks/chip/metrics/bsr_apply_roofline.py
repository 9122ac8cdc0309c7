"""Share of the roofline that one ``bsr`` apply reaches, in percent.

The least time is the larger of the apply's bytes over HBM bandwidth and
its operations over the peak rate, both from ``opcount`` (the algorithm's
count) and ``peaks`` (the device's table). The device time is the union of
the device operations inside the apply's program, averaged over the
applies that started in the traced window.
"""

import opcount
import trace_reduce


def read(ctx):
    runs, secs = trace_reduce.module_runs(ctx["trace"], ctx["module"])
    if not runs or not secs or ctx["peak"] is None:
        return None
    least, _bound = opcount.least_seconds(ctx["flops"], ctx["bytes"], ctx["peak"])
    return 100.0 * least / (secs / runs)
