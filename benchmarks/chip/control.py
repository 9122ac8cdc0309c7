"""Readings of the check's control on the chip, at a cell's own size.

    python3 benchmarks/chip/control.py --workload field16k.batch --seeds 11,12,13

For each seed it draws the columns a run of the cell would compare (the
first ``sample_applies`` column batches of a closed loop, or
``sample_requests`` signals of an open loop's pool) and prints the
relative max error against the reference of:

* ``control``: the reference computed one precision lower (three bf16
  passes, ``reference.py``): the upper reading of the check's limit;
* ``program_lower``: the program with its own lower-precision path
  switched on (``krylov_dtype="bfloat16"`` on ``bsr``; JAX's default
  matmul precision on ``halo``), where the traffic's backend has one.

Benchmark runs never run this.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)

    import jax

    import compare
    import field
    import gen
    import harness
    import reference

    cell, config, traffic = harness.load_cell(args.workload)
    harness.require_tpu(cell["chips"])
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_default_matmul_precision", config["matmul_precision"])
    n, f = config["n_vertices"], config["signal_batch"]
    coords, adj, lmax, _ = harness.make_graph(config)
    coeffs = field.cheb_coefficients(field.sgwt_bank(lmax, config["n_scales"]),
                                     config["order"], lmax)
    filt = harness.build_program(config, {"coords": coords, "adj": adj, "lmax": lmax})
    backend = traffic["backend"]
    opts = dict(traffic.get("opts", {}))
    for seed in (int(s) for s in args.seeds.split(",")):
        if traffic["loop"] == "closed":
            key = gen.jax_key(seed, harness.COLUMNS)
            xs = np.concatenate([np.asarray(gen.columns(key, i, n, f))
                                 for i in range(traffic["sample_applies"])], axis=1)
        else:
            pool = gen.rng(seed, harness.POOL).standard_normal(
                (traffic["n_signals"], n)).astype(np.float32)
            xs = pool[: traffic["sample_requests"]].T.copy()
        ref = reference.reference_apply(adj, xs, coeffs, lmax)
        ctl = reference.reference_apply(adj, xs, coeffs, lmax, precision="bf16x3")
        reading = {"seed": seed, "columns": xs.shape[1],
                   "control": max(compare.rel_max_err(ctl[:, :, k], ref[:, :, k])
                                  for k in range(xs.shape[1]))}
        lower = {"bsr": lambda x: filt.apply(x, backend="bsr", krylov_dtype="bfloat16", **opts)}
        if backend == "halo":
            def halo_default(x):
                with jax.default_matmul_precision("default"):
                    return filt.apply(x, backend="halo", **opts)
            lower["halo"] = halo_default
        if backend in lower:
            got = np.concatenate([np.asarray(lower[backend](xs[:, s:s + f]))
                                  for s in range(0, xs.shape[1], f)], axis=2)
            reading["program_lower"] = max(compare.rel_max_err(got[:, :, k], ref[:, :, k])
                                           for k in range(xs.shape[1]))
        print(json.dumps(reading), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
