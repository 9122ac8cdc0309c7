"""Bring-up smoke of the main path on a TPU: GraphFilter -> ``bsr`` backend
(Pallas Block-ELL kernels) -> AsyncGraphFilterEngine, on a 16,384-sensor
field at the widths of ``configs/sensor_gsp.py`` FULL (F=128 signals, order
M=20, an eta=5 SGWT bank).

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the halo backend over four chips

Every answer is checked against the dense Chebyshev recurrence run under
``jax.default_matmul_precision("highest")``. Any failed phase exits
non-zero; on success the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
There is no CPU fallback: on any other platform it exits before building
anything. It starts no child processes.
"""

from __future__ import annotations

import argparse
import functools
import json
import math
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.configs.sensor_gsp import FULL  # noqa: E402
from repro.core import graph, multipliers  # noqa: E402
from repro.filters import GraphFilter  # noqa: E402
from repro.kernels import autotune  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.serve import AsyncGraphFilterEngine, lasso_panel_solver  # noqa: E402
from repro.solvers import LassoProblem, fista  # noqa: E402

N_SMOKE = 16_384  # the largest field the dense graph build holds on one chip
N_APPLIES = 32
N_SOLVES = 4
SOLVE_ITERS = 40
# Relative max error bounds against the "highest"-precision dense
# reference. F32_BOUND holds f32 Krylov buffers on the Pallas kernels.
# BF16_BOUND is DESIGN.md Sec. 6.3's 16 * 2^-8; it also holds results that
# pass through jnp matmuls at the TPU's default f32 precision, which rounds
# operands to bf16 (the halo backend's local matvec, the bsr adjoint).
F32_BOUND = 1e-4
BF16_BOUND = 16 * 2.0**-8


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def require_tpu(n_chips: int) -> dict:
    """The device check, made before anything is built."""
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu":
        raise SmokeFailure(f"no TPU: JAX reports {info}")
    check(info["count"] >= n_chips, f"need {n_chips} chips, JAX reports {info}")
    return info


def log(**fields) -> None:
    print(" ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


def rel_max_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def build_field(seed: int, n: int):
    """The paper's sensor field at ``n`` sensors, and the eta=5 SGWT filter.

    kappa keeps the paper's connectivity margin: at N=500 its kappa=0.075
    gives pi kappa^2 N = 1.42 ln N, and the same ratio at larger N keeps the
    field connected. (Scaling kappa as 1/sqrt(N), which keeps the mean
    degree at 8.8, leaves a 16,384-sensor field in several components.)
    sigma keeps the paper's sigma/kappa.
    """
    scale = math.sqrt(500.0 * math.log(n) / (n * math.log(500.0)))
    kappa, sigma = FULL.kappa * scale, FULL.sigma * scale
    t0 = time.perf_counter()
    g = graph.connected_sensor_graph(
        jax.random.PRNGKey(seed), n=n, sigma=sigma, kappa=kappa)
    lmax = float(g.lmax_bound())
    filt = GraphFilter.from_multipliers(
        multipliers.sgwt_filter_bank(lmax, FULL.n_scales), order=FULL.order,
        graph=g, lmax=lmax)
    n_edges = g.n_edges
    log(phase="field", N=n, E=n_edges, mean_degree=2 * n_edges / n,
        kappa=kappa, sigma=sigma, lmax=lmax, F=FULL.signal_batch,
        M=FULL.order, eta=filt.eta, build_s=time.perf_counter() - t0)
    log(phase="reduced",
        n_vertices=f"{FULL.n_vertices}->{n} (dense N*N adjacency)",
        kappa=f"{FULL.kappa}->{kappa} (connectivity margin kept)",
        block_size=f"{FULL.block_size}->bsr default")
    return filt


def dense_reference(filt, f):
    """The plain reference: dense Chebyshev recurrence, exact f32 matmuls."""
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        ref = filt.apply(f, backend="dense").block_until_ready()
    log(phase="reference", backend="dense", precision="highest",
        seconds=time.perf_counter() - t0)
    return ref


def compiled_apply(filt, f, **opts):
    """AOT-compile ``filt.apply(f, backend="bsr", **opts)``; the compiled
    program must hold the Mosaic kernel, not an interpreted one."""
    t0 = time.perf_counter()
    compiled = jax.jit(
        functools.partial(filt.apply, backend="bsr", **opts)
    ).lower(f).compile()
    compile_s = time.perf_counter() - t0
    check("tpu_custom_call" in compiled.as_text(),
          f"bsr apply {opts} holds no tpu_custom_call: kernel not compiled")
    return compiled, compile_s


def timed_runs(fn, arg, n: int = 3):
    out, times = None, []
    for _ in range(n):
        t0 = time.perf_counter()
        out = fn(arg).block_until_ready()
        times.append(time.perf_counter() - t0)
    return out, times


def bsr_phase(filt, f, ref) -> None:
    t0 = time.perf_counter()
    state = filt.prepare_backend("bsr")
    bell = state.bell
    tiling = autotune.select_tiling(
        state.n_pad, f.shape[1], filt.eta, bell.n_block_rows, bell.k_max,
        bell.block_size, f.dtype)
    log(phase="bsr_prepare", block_size=bell.block_size,
        n_block_rows=bell.n_block_rows, k_max=bell.k_max,
        nnz_blocks=bell.nnz_blocks, tiling=tiling,
        seconds=time.perf_counter() - t0)

    for kd, bound in (("float32", F32_BOUND), ("bfloat16", BF16_BOUND)):
        compiled, compile_s = compiled_apply(filt, f, krylov_dtype=kd)
        first_t0 = time.perf_counter()
        out = compiled(f).block_until_ready()
        first_s = time.perf_counter() - first_t0
        out, warm = timed_runs(compiled, f)
        check(out.shape == ref.shape, f"bsr {kd}: shape {out.shape}")
        err = rel_max_err(out, ref)
        log(phase="bsr_apply", krylov_dtype=kd, tpu_custom_call=True,
            compile_s=compile_s, first_s=first_s, warm_s=warm,
            rel_max_err=err, bound=bound)
        check(err <= bound, f"bsr {kd}: rel max err {err} > {bound}")


def engine_phase(filt, f, ref, seed: int) -> None:
    solver = lasso_panel_solver(filt, n_iters=SOLVE_ITERS)
    eng = AsyncGraphFilterEngine(filt, backend="bsr", solver=solver)
    signals = np.asarray(f[:, :N_APPLIES])
    rng = np.random.default_rng(seed)
    noisy = (np.asarray(f[:, :N_SOLVES])
             + 0.5 * rng.standard_normal((f.shape[0], N_SOLVES))
             ).astype(np.float32)

    t0 = time.perf_counter()
    applies = [eng.submit(signals[:, i]) for i in range(N_APPLIES)]
    solves = [eng.submit_solve(noisy[:, i]) for i in range(N_SOLVES)]
    apply_out = [eng.wait(t) for t in applies]
    solve_out = [eng.wait(t) for t in solves]
    serve_s = time.perf_counter() - t0

    errs = []
    for i, out in enumerate(apply_out):
        check(out.shape == ref.shape[:2], f"apply {i}: shape {out.shape}")
        errs.append(rel_max_err(out, ref[:, :, i]))
    check(max(errs) <= F32_BOUND,
          f"engine applies: rel max err {max(errs)} > {F32_BOUND}")

    # Reference solve: the same fixed-budget FISTA on the dense backend at
    # "highest" precision. Run eagerly: a jit closing over the dense N*N
    # Laplacian would embed it in the executable as a constant.
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        x_ref = np.asarray(fista(
            LassoProblem(filt=filt, y=jnp.asarray(noisy), mu=1.0),
            n_iters=SOLVE_ITERS, backend="dense").x)
    log(phase="reference_solve", backend="dense", precision="highest",
        seconds=time.perf_counter() - t0)
    solve_errs = []
    for i, res in enumerate(solve_out):
        check(res.x.shape == (f.shape[0],), f"solve {i}: shape {res.x.shape}")
        check(bool(np.all(np.isfinite(res.x))), f"solve {i}: non-finite x")
        check(res.history[-1] < res.history[0],
              f"solve {i}: objective did not decrease")
        solve_errs.append(rel_max_err(res.x, x_ref[:, i]))
    check(max(solve_errs) <= BF16_BOUND,
          f"engine solves: rel max err {max(solve_errs)} > {BF16_BOUND}")

    stats = eng.stats()
    log(phase="engine", serve_s=serve_s, applies_checked=len(errs),
        apply_rel_max_err=max(errs), solves_checked=len(solve_errs),
        solve_rel_max_err=max(solve_errs), served=stats["served"],
        apply_panels=stats["applies"], solved=stats["solved"],
        solve_panels=stats["solves"], recompiles=stats["recompiles"],
        pad_waste=stats["pad_waste"])
    check(stats["served"] == N_APPLIES and stats["solved"] == N_SOLVES,
          f"engine counts {stats}")


def halo_phase(filt, f, ref, n_chips: int) -> None:
    """The paper's distributed apply over ``n_chips`` devices, both
    schedules, against the one-device dense reference."""
    t0 = time.perf_counter()
    ctx = filt.prepare_backend("halo", n_parts=n_chips)
    log(phase="halo_prepare", n_parts=n_chips, n_local=ctx.plan.n_local,
        halo_words=ctx.plan.halo_words, seconds=time.perf_counter() - t0)

    shards = ctx.scatter_signal(f).addressable_shards
    devices = [str(s.device) for s in shards]
    log(phase="halo_shards", devices=devices)
    check(len(set(devices)) == n_chips,
          f"{n_chips} shards on {len(set(devices))} distinct devices")

    outs = {}
    for overlap in (True, False):
        schedule = "overlapped" if overlap else "serial"
        t0 = time.perf_counter()
        out = filt.apply(f, backend="halo", n_parts=n_chips,
                         overlap=overlap).block_until_ready()
        first_s = time.perf_counter() - t0
        out, warm = timed_runs(
            functools.partial(filt.apply, backend="halo", n_parts=n_chips,
                              overlap=overlap), f)
        err = rel_max_err(out, ref)
        log(phase="halo_apply", schedule=schedule, first_s=first_s,
            warm_s=warm, rel_max_err=err, bound=BF16_BOUND)
        check(err <= BF16_BOUND, f"halo {schedule}: rel max err {err}")
        outs[schedule] = out

    words = filt.messages_per_apply(backend="halo", n_parts=n_chips)
    log(phase="halo_words", messages_per_apply=words,
        order_times_halo_words=filt.order * ctx.plan.halo_words,
        radio_bound_2ME=2 * filt.order * filt.graph.n_edges,
        overlapped_vs_serial=rel_max_err(outs["overlapped"], outs["serial"]))
    check(words == filt.order * ctx.plan.halo_words,
          "messages_per_apply disagrees with the plan's halo_words")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the halo backend across four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    info = require_tpu(args.chips)
    log(phase="device", **info, cache_dir=use_compile_cache())
    filt = build_field(args.seed, N_SMOKE)
    f = jax.random.normal(
        jax.random.PRNGKey(args.seed + 1), (N_SMOKE, FULL.signal_batch),
        jnp.float32)
    ref = dense_reference(filt, f)
    if args.chips == 1:
        bsr_phase(filt, f, ref)
        engine_phase(filt, f, ref, args.seed)
    else:
        halo_phase(filt, f, ref, args.chips)
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
