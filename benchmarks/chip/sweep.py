"""Find the knee of an open-loop cell: one set-up, then the window at each
offered rate in turn, on the chip.

    python3 benchmarks/chip/sweep.py --workload field2k.serve --seed 5 --seconds 8 \\
        --rates 2000,3000,4000,5000,6000

Prints one JSON line per rate: p50/p95 latency, p95 of the first and the
last fifth of the requests (a growing backlog shows as the second far
above the first), the backlog when the last request arrived, refusals and
the generator's lateness. The knee is the highest rate whose p95 stays
under the cell's limit without a growing backlog; the cell runs at 0.8x it.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests per second")
    args = ap.parse_args(argv)

    import harness

    st = harness.setup_cell(args.workload, args.seed)
    loop = st["loop"]
    print(json.dumps({"setup_s": time.perf_counter() - T_START}), flush=True)
    for rate in (float(x) for x in args.rates.split(",")):
        loop.traffic = dict(loop.traffic, rate_per_s=rate)
        res = loop.window(args.seconds, False)
        print(json.dumps({"rate_per_s": rate, **{k: v for k, v in res.items() if k != "e2e"},
                          **res["e2e"], "counters": loop.counters()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
