"""XLA compiles plus persistent-cache loads inside the window, from the
program's compile counter (``repro.obs``, always on): those made while the
``--trace 1`` run's profile of the window was recorded. Set-up warms every
shape, so 0 is the expected reading, and a reading. Each one found is
logged to stderr with its function's name. A program without
``repro.obs`` gives nothing.
"""

import json
import sys


def read(ctx):
    obs = sys.modules.get("repro.obs")
    if obs is None:
        return None
    snap = obs.snapshot()
    for e in snap["events"]:
        if e["profiled"] and e["kind"] != "trace":
            print("window compile", json.dumps({k: e[k] for k in ("kind", "fun", "s", "span")}),
                  file=sys.stderr, flush=True)
    c = snap["compiles"]
    return float(c["compile"]["profiled_n"] + c["cache_load"]["profiled_n"])
