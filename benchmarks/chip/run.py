"""Run one cell of the on-chip benchmark once.

    python3 benchmarks/chip/run.py --workload field16k.batch --seed 7 --seconds 10 --trace 0

The cell, its configuration and its traffic are read from BENCHMARK.json
and the files it names. The last line of stdout is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` ``breakdown``, and last ``compared``: each
number the check compared, with its limit. The same numbers close stderr.
On any platform but TPU, or with fewer chips than the cell asks for, it
exits with code 3 and prints no result.
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
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-trace", default=None,
                    help="also write the traced planes as gzipped JSON to this path")
    args = ap.parse_args(argv)

    import harness

    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                                  t_start=T_START, dump_trace=args.dump_trace)
    except harness.NoChip as e:
        print(f"refused: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
