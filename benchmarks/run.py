"""Benchmark harness: one function per paper table/figure + system
benchmarks. Prints ``name,us_per_call,derived`` CSV rows. Timings are
of the CPU or interpret-mode Pallas this runs on; the chip's numbers come
from ``benchmarks/chip/`` alone.

  fig4_cheb_approx     paper Fig. 4  — multiplier approximation vs order M
  tab_denoising        paper Sec.V-B — noisy vs denoised MSE (0.250/0.013)
  tab_comm_scaling     paper Sec.IV  — message counts vs network size
  tab_wavelet_ista     paper Sec.V-C — SGWT lasso denoising + comm costs
  tab_gossip           gossip consensus contraction + bytes vs all-reduce
  tab_kernel           Pallas step chain vs jnp reference (interpret mode)
  tab_filter_backends  GraphFilter backend parity vs the dense oracle
  tab_solvers          solver layer — ISTA vs FISTA vs CG on the Sec. V-C
                       benchmark graph: iterations-to-tolerance, wall
                       time, words/iteration per backend
  tab_streaming        streaming lane — full refilter vs delta filtering
                       (words/frame + wall time vs change fraction, output
                       parity) and warm-started vs cold solver iterations
  tab_engine           serving engines under load (benchmarks/loadgen.py):
                       async continuous-batching vs the sync micro-batcher
                       — capacity, p50/p99 at an equal live rate, steady-
                       state recompiles, pad waste
  tab_churn            topology churn (repro.dynamic, DESIGN.md Sec. 10):
                       mobile-sensor convoy scenario — incremental frame
                       latency + words and plan-repair latency vs the full
                       re-partition + re-filter baseline, parity vs the
                       dense oracle, steady-state churn-kernel retraces
  tab_roofline         summary of the dry-run roofline table (if present)

Run: PYTHONPATH=src python -m benchmarks.run [--full] [--only SUBSTR]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.apps import wavelet_denoise_ista
from repro.core import chebyshev, gossip, graph, multipliers
from repro.core.distributed import DistributedGraphContext, build_partition_plan
from repro.filters import GraphFilter
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.launch.compile_cache import use_compile_cache
from repro.solvers import (
    GramProblem,
    LassoProblem,
    cheb_inverse,
    cheb_preconditioner,
    conjugate_gradient,
    fista,
    ista,
)
from repro.stream import StreamingFilter, StreamingWiener

def row(name: str, us: float, derived: str) -> None:
    """Emit one CSV row."""
    print(f"{name},{us:.1f},{derived}", flush=True)


def _timeit(fn, n=3):
    fn()  # compile
    t0 = time.perf_counter()
    for _ in range(n):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / n * 1e6


# ---------------------------------------------------------------- fig 4 --


def fig4_cheb_approx(full: bool) -> None:
    g = graph.connected_sensor_graph(jax.random.PRNGKey(0), n=500)
    lap = np.asarray(g.laplacian(), np.float64)
    lam = np.linalg.eigvalsh(lap)
    lmax = float(g.lmax_bound())
    mult = multipliers.tikhonov(1.0, 1)
    exact = mult(lam)
    for m in (5, 10, 15, 20, 30, 40):
        c = chebyshev.cheb_coefficients([mult], m, lmax)
        approx = chebyshev.cheb_eval(c[0], lam, lmax)
        sup = float(np.max(np.abs(approx - exact)))
        row(f"fig4_cheb_approx_M{m}", 0.0, f"sup_err={sup:.2e}")


# ----------------------------------------------------------- denoising --


def tab_denoising(full: bool) -> None:
    """Paper Sec. V-B: 500 sensors, tau=r=1, M=20; 1000 trials in the
    paper (noisy 0.250 / denoised 0.013). Default here: 100 trials."""
    trials = 1000 if full else 100
    key = jax.random.PRNGKey(0)
    noisy_mse, den_mse = [], []
    t0 = time.perf_counter()
    for _ in range(trials):
        key, kg, kn = jax.random.split(key, 3)
        g = graph.connected_sensor_graph(kg, n=500)
        f0 = g.coords[:, 0] ** 2 + g.coords[:, 1] ** 2 - 1.0
        y = f0 + 0.5 * jax.random.normal(kn, f0.shape)
        lmax = float(g.lmax_bound())
        op = GraphFilter.from_multipliers(
            [multipliers.tikhonov(1.0, 1)], 20, graph=g, lmax=lmax)
        fhat = op.apply(y, backend="dense")[0]
        noisy_mse.append(float(jnp.mean((y - f0) ** 2)))
        den_mse.append(float(jnp.mean((fhat - f0) ** 2)))
    us = (time.perf_counter() - t0) / trials * 1e6
    row("tab_denoising", us,
        f"trials={trials};noisy_mse={np.mean(noisy_mse):.4f}"
        f";denoised_mse={np.mean(den_mse):.4f}"
        f";paper=0.250/0.013")


# ------------------------------------------------------- comm scaling --


def tab_comm_scaling(full: bool) -> None:
    """Paper Sec. IV: per-apply words. radio bound 2M|E| vs mesh halo vs
    all-gather baseline, across network sizes (8 partitions)."""
    order = 20
    for n in (250, 500, 1000, 2000) if full else (250, 500, 1000):
        kappa = 0.075 * float(np.sqrt(500.0 / n))
        g = graph.connected_sensor_graph(
            jax.random.PRNGKey(n), n=n, sigma=kappa * 0.99, kappa=kappa)
        plan = build_partition_plan(g.adjacency, g.coords, 8)
        radio = 2 * order * g.n_edges
        halo = order * plan.halo_words
        ag = order * plan.n_local * 8 * 7
        row(f"tab_comm_scaling_N{n}", 0.0,
            f"edges={g.n_edges};radio_2ME={radio};halo={halo};allgather={ag}")


# ---------------------------------------------------------- wavelet ----


def tab_wavelet_ista(full: bool) -> None:
    key = jax.random.PRNGKey(3)
    kg, kn = jax.random.split(key)
    g = graph.connected_sensor_graph(kg, n=500)
    f0 = g.coords[:, 0] ** 2 + g.coords[:, 1] ** 2 - 1.0
    y = f0 + 0.5 * jax.random.normal(kn, f0.shape)
    lmax = float(g.lmax_bound())
    n_scales, order, iters = 4, 20, 40

    t0 = time.perf_counter()
    fhat, a = wavelet_denoise_ista(
        g, y, lmax, n_scales=n_scales, order=order,
        mu=2.0, n_iters=iters)
    us = (time.perf_counter() - t0) * 1e6
    # Sec. V-C communication model per ISTA iteration:
    e, eta = g.n_edges, n_scales + 1
    per_iter = 2 * order * e * eta + 2 * order * e
    row("tab_wavelet_ista", us,
        f"denoised_mse={float(jnp.mean((fhat - f0)**2)):.4f}"
        f";noisy_mse={float(jnp.mean((y - f0)**2)):.4f}"
        f";sparsity={float(jnp.mean(a == 0.0)):.3f}"
        f";words_per_iter={per_iter}")


# ------------------------------------------------------------ gossip ---


_TRAIN_WORKER: dict | None = None


def _train_worker(full: bool) -> dict:
    """Timed distributed rows come from ``benchmarks/train_bench.py`` run
    once in a subprocess with 8 forced host devices (the bench driver
    itself owns only the default device set); output cached across the
    ``tab_gossip`` / ``tab_train`` tables."""
    global _TRAIN_WORKER
    if _TRAIN_WORKER is None:
        script = Path(__file__).resolve().parent / "train_bench.py"
        env = dict(os.environ)
        env["PYTHONPATH"] = str(script.parent.parent / "src")
        env.pop("XLA_FLAGS", None)  # worker forces its own device count
        # A CPU-only tool: this process may hold the accelerator already.
        env["JAX_PLATFORMS"] = "cpu"
        cmd = [sys.executable, str(script)] + (["--full"] if full else [])
        proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                              timeout=1800, check=True)
        _TRAIN_WORKER = json.loads(proc.stdout.strip().splitlines()[-1])
    return _TRAIN_WORKER


def _emit_worker_rows(full: bool, prefix: str) -> None:
    for r in _train_worker(full)["rows"]:
        if r["name"].startswith(prefix):
            row(r["name"], r["us"], r["derived"])


def tab_gossip(full: bool) -> None:
    """Measured on a real 8-device mesh (subprocess): Chebyshev-gossip
    tree sync vs exact all-reduce mean, with executed-schedule word counts
    (f32 vs bf16 payloads) cross-checked against the analytic model."""
    _emit_worker_rows(full, "gossip_")


def tab_train(full: bool) -> None:
    """Decentralized-training step times, measured (DESIGN.md Sec. 12.5):
    per-leaf serial gossip vs bucketed overlap pipeline under emulated
    per-message launch latency; all-reduce reference + loss parity; and
    the induced-straggler run where truncated gossip beats the barrier."""
    _emit_worker_rows(full, "train_")


# ------------------------------------------------------------ kernel ---


def tab_kernel(full: bool) -> None:
    g = graph.connected_sensor_graph(jax.random.PRNGKey(7), n=480,
                                     sigma=0.075, kappa=0.076)
    lap = np.asarray(g.laplacian())
    order_perm = graph.spatial_partition_order(np.asarray(g.coords), 60)
    lap = lap[np.ix_(order_perm, order_perm)]
    mat = kref.bsr_from_dense(lap, 8)
    lmax = float(g.lmax_bound())
    coeffs = chebyshev.cheb_coefficients(
        [multipliers.tikhonov(1.0, 1)], 20, lmax)
    f = jax.random.normal(jax.random.PRNGKey(8), (mat.n, 8))

    def pallas_path():
        return kops.cheb_apply_bsr(mat.blocks, mat.rows, mat.cols, f, coeffs, lmax,
                                   interpret=True)

    def ref_path():
        return kref.cheb_apply_bsr_ref(mat, f, coeffs, lmax)

    us_ref = _timeit(jax.jit(ref_path))
    got = pallas_path()
    want = ref_path()
    err = float(jnp.max(jnp.abs(got - want)))
    dens = mat.n_tiles / mat.n_block_rows**2
    row("tab_kernel_cheb_bsr", us_ref,
        f"max_err={err:.1e};block_density={dens:.3f}"
        f";n_tiles={mat.n_tiles};interpret_validated=1")


# --------------------------------------------------- filter backends ---


def tab_filter_backends(full: bool) -> None:
    """Unified GraphFilter layer: per-backend parity vs the dense oracle."""
    g = graph.connected_sensor_graph(jax.random.PRNGKey(5), n=480,
                                     sigma=0.075, kappa=0.076)
    filt = GraphFilter.from_multipliers(
        [multipliers.tikhonov(1.0, 1), multipliers.heat(0.5)],
        order=20, graph=g)
    f = jax.random.normal(jax.random.PRNGKey(6), (g.n_vertices, 8))
    ref_out = filt.apply(f, backend="dense")

    outs, times = {}, {}
    for be in ("bsr", "halo", "allgather"):
        outs[be] = filt.apply(f, backend=be)  # warm: prepare + compile
        times[be] = _timeit(lambda be=be: filt.apply(f, backend=be))
        err = float(jnp.max(jnp.abs(outs[be] - ref_out)))
        row(f"tab_filter_backend_{be}", times[be],
            f"max_err_vs_dense={err:.1e}")

    # Overlapped vs serial halo schedule (DESIGN.md Sec. 6.4): the halo
    # row above is the overlapped default; time the serial reference and
    # pin schedule parity. halo_overlap re-emits the default's timing
    # under its explicit name.
    out_serial = filt.apply(f, backend="halo", overlap=False)
    us_serial = _timeit(lambda: filt.apply(f, backend="halo", overlap=False))
    sched_err = float(jnp.max(jnp.abs(outs["halo"] - out_serial)))
    row("tab_filter_backend_halo_overlap", times["halo"],
        f"overlap_vs_serial={sched_err:.1e}"
        f";speedup_vs_serial={us_serial / max(times['halo'], 1e-9):.2f}x")
    row("tab_filter_backend_halo_serial", us_serial,
        f"max_err_vs_dense="
        f"{float(jnp.max(jnp.abs(out_serial - ref_out))):.1e}")

    # bf16 Krylov buffers on the bsr path (f32 combine accumulators).
    out_bf16 = filt.apply(f, backend="bsr", krylov_dtype="bfloat16")
    us_bf16 = _timeit(
        lambda: filt.apply(f, backend="bsr", krylov_dtype="bfloat16"))
    rel = float(jnp.max(jnp.abs(out_bf16 - outs["bsr"]))
                / jnp.max(jnp.abs(outs["bsr"])))
    row("tab_filter_backend_bsr_bf16", us_bf16,
        f"rel_err_vs_f32={rel:.1e};bound=6.3e-2")

    # grid backend on its native topology
    gg = graph.grid_graph(32)
    gf = GraphFilter.from_multipliers(
        [multipliers.tikhonov(1.0, 1)], order=20, graph=gg, lmax=8.0)
    xg = jax.random.normal(jax.random.PRNGKey(8), (gg.n_vertices, 8))
    err = float(jnp.max(jnp.abs(
        gf.apply(xg, backend="grid") - gf.apply(xg, backend="dense"))))
    row("tab_filter_backend_grid", 0.0, f"max_err_vs_dense={err:.1e}")


# ----------------------------------------------------------- solvers ---


def tab_solvers(full: bool) -> None:
    """Solver layer on the Sec. V-C benchmark (500-node sensor graph, 3
    scales, order 20): ISTA vs FISTA iterations-to-tolerance and wall
    time; the FISTA half-iterations claim at matched objective; CG inverse
    filtering on the Gram operator; and words/iteration per backend (halo
    plan accounting vs the all-gather baseline vs the paper radio bound).
    """
    key = jax.random.PRNGKey(42)
    kg, kn = jax.random.split(key)
    g = graph.connected_sensor_graph(kg, n=500)
    f0 = g.coords[:, 0] ** 2 + g.coords[:, 1] ** 2 - 1.0
    y = f0 + 0.5 * jax.random.normal(kn, f0.shape)
    lmax = float(g.lmax_bound())
    n_scales, order, mu = 3, 20, 2.0
    bank = multipliers.sgwt_filter_bank(lmax, n_scales=n_scales)
    filt = GraphFilter.from_multipliers(bank, order, graph=g, lmax=lmax)
    problem = LassoProblem(filt=filt, y=y, mu=mu)

    # Per-backend words/iteration (8 partitions; one length-1 forward +
    # one length-eta adjoint per lasso iteration). Derived from the
    # partition plan directly because an 8-part halo state cannot be
    # prepared on this single-device benchmark host (the mesh needs 8
    # devices); it is the same `order * halo_words` model
    # backends.messages_per_apply evaluates, and the 8-device subprocess
    # test cross-checks SolveResult.messages_per_iteration live.
    plan = build_partition_plan(g.adjacency, g.coords, 8)
    m_halo = order * plan.halo_words
    m_ag = order * plan.n_local * 8 * 7
    m_radio = 2 * order * g.n_edges
    lasso_words = {
        "dense": 0,
        "halo": m_halo * (1 + filt.eta),
        "allgather": m_ag * (1 + filt.eta),
        "radio_bound": m_radio * (1 + filt.eta),
    }

    # Iterations to a matched objective, measured from the recorded
    # history (a relative-change stopping rule would flatter ISTA: its
    # O(1/k) tail makes tiny per-iteration progress look like
    # convergence while FISTA is still descending fast).
    budget = 300 if full else 150
    results, walls = {}, {}
    for method, fn in (("ista", ista), ("fista", fista)):
        # Warm with the SAME iteration count: a different-length scan is a
        # different program, and timing it would clock trace+compile.
        fn(problem, n_iters=budget)
        t0 = time.perf_counter()
        results[method] = fn(problem, n_iters=budget)
        walls[method] = (time.perf_counter() - t0) * 1e6
    # Anchor the target at what ISTA achieves with the full budget; the
    # interesting number is how few iterations (hence words) FISTA needs
    # to match it.
    target = float(results["ista"].history.min())
    for method, res in results.items():
        # history[j] is the objective of the iterate after j update
        # iterations (history[0] = the zero-iteration warm start), so the
        # first index at target IS the iteration count. Caveat: FISTA's
        # history monitors the extrapolated point z_k (free to record),
        # not a_k, so its crossing is approximate by O(momentum step) —
        # the exact-objective check at matched budgets lives in
        # tests/test_solvers.py::test_fista_half_iterations_sec_vc and
        # the fista_half_iters row below.
        hit = np.nonzero(res.history <= target)[0]
        iters_to_target = int(hit[0]) if hit.size else budget
        obj = problem.objective(res.aux)
        row(f"tab_solvers_{method}", walls[method],
            f"iters_to_matched_obj={iters_to_target}"
            f";target_obj={target:.4f};final_obj={obj:.4f}"
            f";budget={budget}"
            f";words_to_matched_obj_halo="
            f"{lasso_words['halo'] * iters_to_target}")

    # The headline claim: FISTA reaches ISTA's 40-iteration objective in
    # <= 20 iterations (same words/iteration -> half the communication).
    res_i = ista(problem, n_iters=40)
    res_f = fista(problem, n_iters=20)
    obj_i = problem.objective(res_i.aux)
    obj_f = problem.objective(res_f.aux)
    row("tab_solvers_fista_half_iters", 0.0,
        f"ista40_obj={obj_i:.4f};fista20_obj={obj_f:.4f}"
        f";fista_at_half_wins={int(obj_f <= obj_i)}")

    # CG inverse filtering: recover f0 from the union's stacked outputs.
    obs = filt.apply(jnp.asarray(f0))
    gram_problem = GramProblem(filt=filt, b=filt.adjoint(obs), reg=1e-6)
    conjugate_gradient(gram_problem, n_iters=budget, tol=1e-6)  # warm
    t0 = time.perf_counter()
    res_cg = conjugate_gradient(gram_problem, n_iters=budget, tol=1e-6)
    us = (time.perf_counter() - t0) * 1e6
    rec_err = float(jnp.max(jnp.abs(res_cg.x - f0)))
    cg_words = {"halo": 2 * m_halo, "radio_bound": 2 * m_radio}
    row("tab_solvers_cg_inverse", us,
        f"iters_to_tol={res_cg.iterations};tol=1e-6"
        f";max_rec_err={rec_err:.1e};converged={int(res_cg.converged)}"
        f";words_per_iter_halo={cg_words['halo']}")

    # Chebyshev-preconditioned CG (DESIGN.md Sec. 11.3): the fit
    # q(L) ~= 1/(h + reg) is built once from gram_coeffs, each PCG
    # iteration pays K extra matvecs, and the acceptance bits are
    # pcg_halves (iterations <= 0.5x plain CG) and fewer_total_words —
    # the solver_precond_* rows carry both.
    pre = cheb_preconditioner(gram_problem, order=32)
    conjugate_gradient(gram_problem, n_iters=budget, tol=1e-6,
                       preconditioner=pre)  # warm
    t0 = time.perf_counter()
    res_pcg = conjugate_gradient(gram_problem, n_iters=budget, tol=1e-6,
                                 preconditioner=pre)
    us_p = (time.perf_counter() - t0) * 1e6
    k_pre = pre.orders[0]
    pcg_per_iter = cg_words["halo"] + k_pre * plan.halo_words
    total_pcg = pcg_per_iter * res_pcg.iterations
    total_cg = cg_words["halo"] * res_cg.iterations
    row("solver_precond_pcg", us_p,
        f"iters_to_tol={res_pcg.iterations};tol=1e-6"
        f";plain_cg_iters={res_cg.iterations}"
        f";pcg_halves={int(res_pcg.iterations <= res_cg.iterations // 2)}"
        f";fit_order={k_pre};fit_rate={pre.rate:.4f}"
        f";words_per_iter_halo={pcg_per_iter}"
        f";total_words_halo={total_pcg};plain_total_words={total_cg}"
        f";fewer_total_words={int(total_pcg < total_cg)}"
        f";converged={int(res_pcg.converged)}")

    # Standalone fixed-point inverse: rate known at build time, no
    # inner-product reductions (pure filter applies per sweep).
    res_fp = cheb_inverse(gram_problem, order=16, n_iters=budget, tol=1e-6)
    t0 = time.perf_counter()
    res_fp = cheb_inverse(gram_problem, order=16, n_iters=budget, tol=1e-6)
    us_f = (time.perf_counter() - t0) * 1e6
    k_fp = res_fp.aux.orders[0]
    fp_per_iter = cg_words["halo"] + k_fp * plan.halo_words
    row("solver_precond_cheb_inverse", us_f,
        f"iters_to_tol={res_fp.iterations};tol=1e-6"
        f";fit_order={k_fp};fit_rate={res_fp.aux.rate:.4f}"
        f";predicted_iters="
        f"{int(np.ceil(np.log(1e-6) / np.log(res_fp.aux.rate)))}"
        f";converged={int(res_fp.converged)}"
        f";words_per_iter_halo={fp_per_iter}")

    for be, w in lasso_words.items():
        row(f"tab_solvers_words_{be}", 0.0,
            f"lasso_words_per_iter={w};P=8")


# ---------------------------------------------------------- streaming --


def tab_streaming(full: bool) -> None:
    """Streaming lane (DESIGN.md Sec. 8). Delta rows: an 80x80 grid scene
    (N=6400, order 20, 8 partitions) where a square patch of vertices
    changes between frames — per-frame halo words and wall time for delta
    filtering vs a full refilter across change fractions, with output
    parity vs the full apply. Warm-start rows: cold vs seeded solver
    iterations on the Sec. V-C sensor benchmark (the ISSUE-4 acceptance
    rows)."""
    rng = np.random.default_rng(11)
    side, order, n_parts = 80, 20, 8
    gg = graph.grid_graph(side)
    n = side * side
    filt = GraphFilter.from_multipliers(
        [multipliers.tikhonov(1.0, 1)], order, graph=gg, lmax=8.0)
    f0 = (np.asarray(gg.coords[:, 0] ** 2 + gg.coords[:, 1] ** 2,
                     np.float32))

    lane = StreamingFilter(filt, backend="dense", n_parts=n_parts,
                           max_delta_frac=0.5)
    lane.push(f0)  # cold frame
    words_full = lane._full_words()

    def timed_push(y):
        # Best of 3 replays; the first pays the bucket's compile and the
        # min discards it (plus any descheduling blip on a shared host).
        best, res = None, None
        for _ in range(3):
            lane.reset()
            lane.push(f0)
            t0 = time.perf_counter()
            res = lane.push(y)
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        return res, best * 1e6

    us_full = _timeit(lambda: filt.apply(jnp.asarray(f0), backend="dense"))
    row("tab_streaming_full_refilter", us_full,
        f"words_per_frame={words_full}")

    for frac, patch in ((0.02, 11), (0.05, 18), (0.10, 25), (0.25, 40)):
        y = f0.copy()
        r0, c0 = rng.integers(0, side - patch, size=2)
        rr, cc = np.meshgrid(np.arange(r0, r0 + patch),
                             np.arange(c0, c0 + patch), indexing="ij")
        ch = (rr * side + cc).ravel()
        y[ch] += rng.normal(size=len(ch)).astype(np.float32) * 0.3
        res, us = timed_push(y)
        parity = float(np.max(np.abs(
            res.out - np.asarray(filt.apply(jnp.asarray(y),
                                            backend="dense")))))
        row(f"tab_streaming_delta_c{int(frac * 100):02d}", us,
            f"mode={res.mode};changed={res.changed};active={res.active}"
            f";words_per_frame={res.words};words_full={words_full}"
            f";words_ratio={res.words / words_full:.3f}"
            f";parity_vs_full={parity:.1e}")

    # Warm-started solvers on a slowly varying scene (Sec. V-C sensor
    # benchmark): frame 1 perturbs 2% of frame 0's vertices. (a)
    # Wiener/CG: iterations to tol, cold vs seeded with frame 0's latent.
    # (b) FISTA: iterations until the warm run's objective history
    # crosses the cold run's final objective.
    g = graph.connected_sensor_graph(jax.random.PRNGKey(11), n=500)
    ns = g.n_vertices
    fs = np.asarray(g.coords[:, 0] ** 2 + g.coords[:, 1] ** 2 - 1.0,
                    np.float32)
    y0 = fs + 0.5 * rng.normal(size=ns).astype(np.float32)
    y1 = y0.copy()
    ch = rng.choice(ns, size=ns // 50, replace=False)
    y1[ch] += 0.3 * rng.normal(size=len(ch)).astype(np.float32)

    wfilt = GraphFilter.from_multipliers(
        [multipliers.heat(0.5)], order, graph=g)
    wlane = StreamingWiener(wfilt, 0.25, tol=1e-6, n_iters=200)
    it0 = wlane.push(y0).iterations
    t0 = time.perf_counter()
    it1 = wlane.push(y1).iterations
    us = (time.perf_counter() - t0) * 1e6
    wlane.reset()
    cold1 = wlane.push(y1).iterations
    row("tab_streaming_warm_wiener", us,
        f"cold_iters={cold1};warm_iters={it1};frame0_iters={it0}"
        f";tol=1e-6;saved={cold1 - it1}")

    lmax = float(g.lmax_bound())
    sfilt = GraphFilter.from_multipliers(
        multipliers.sgwt_filter_bank(lmax, n_scales=3), order,
        graph=g, lmax=lmax)
    budget = 120
    p1 = LassoProblem(filt=sfilt, y=jnp.asarray(y1), mu=2.0)
    cold0 = fista(LassoProblem(filt=sfilt, y=jnp.asarray(y0), mu=2.0),
                  n_iters=budget)
    coldr = fista(p1, n_iters=budget)
    warmr = fista(p1, a0=cold0.aux, n_iters=budget)
    target = float(coldr.history[-1]) * (1.0 + 1e-6)
    hit = np.nonzero(warmr.history <= target)[0]
    warm_iters = int(hit[0]) if hit.size else budget
    row("tab_streaming_warm_fista", 0.0,
        f"cold_iters={budget};warm_iters_to_cold_obj={warm_iters}"
        f";target_obj={target:.4f}"
        f";warm_final_obj={p1.objective(warmr.aux):.4f}")


# ------------------------------------------------------------- engine --


def tab_engine(full: bool) -> None:
    """Serving engines under the loadgen workload (DESIGN.md Sec. 9.4).

    One deterministic mixed-lane trace (90% applies / 8% solves / 2%
    frames, hot-spot stream skew) replayed through the async
    continuous-batching engine and the pr6 synchronous micro-batcher,
    both warm (the trace replays once unmeasured first, so recompiles
    are steady-state and capacity excludes compile time):

    * ``engine_*_capacity`` — warm burst (every request at t=0, panels
      always full): timing column is busy us per request, derived
      carries capacity (requests/s of pure service time).
    * ``engine_*_paced`` — the same engines at an equal live Poisson
      rate both can sustain: timing column is virtual-clock p99 us.
    * ``engine_summary`` — the acceptance row: async/sync capacity
      ratio (>=5x), p99 comparison at the equal rate, steady-state
      recompile count (0 when the bucket cache works).
    """
    from benchmarks import loadgen

    n, order, streams = 256, 20, 100_000
    kappa = 0.075 * float(np.sqrt(500.0 / n))
    g = graph.connected_sensor_graph(
        jax.random.PRNGKey(0), n=n, sigma=kappa * 0.99, kappa=kappa)
    filt = GraphFilter.from_multipliers(
        [multipliers.tikhonov(1.0, 1)], order, graph=g)
    pool = loadgen.make_signal_pool(n, 64)

    reqs = 4000 if full else 1000
    burst = loadgen.make_trace(streams, reqs / 500.0, 500.0, burst=True)
    caps = {}
    for kind in ("async", "sync"):
        rep = caps[kind] = loadgen.run_load(
            burst, filt, engine=kind, warm=True, pool=pool)
        row(f"engine_{kind}_capacity",
            1e6 * rep.busy_s / max(rep.served, 1),
            f"capacity_rps={rep.capacity_rps:.0f};served={rep.served}"
            f";panels={rep.panels};recompiles={rep.recompiles}"
            f";pad_waste={rep.pad_waste:.3f}")

    paced = loadgen.make_trace(streams, (reqs // 4) / 60.0, 60.0)
    p99s = {}
    for kind in ("async", "sync"):
        rep = p99s[kind] = loadgen.run_load(
            paced, filt, engine=kind, warm=True, pool=pool)
        row(f"engine_{kind}_paced", 1e3 * rep.p99_ms,
            f"rate_rps=60;p50_ms={rep.p50_ms:.3f};p99_ms={rep.p99_ms:.3f}"
            f";throughput_rps={rep.throughput_rps:.0f}"
            f";recompiles={rep.recompiles}")

    speedup = caps["async"].capacity_rps / max(caps["sync"].capacity_rps, 1e-9)
    row("engine_summary", 0.0,
        f"throughput_x={speedup:.1f};accept_ge_5x={int(speedup >= 5.0)}"
        f";async_p99_ms={p99s['async'].p99_ms:.3f}"
        f";sync_p99_ms={p99s['sync'].p99_ms:.3f}"
        f";p99_no_worse={int(p99s['async'].p99_ms <= p99s['sync'].p99_ms)}"
        f";steady_recompiles={caps['async'].recompiles}")


# -------------------------------------------------------------- churn --


def tab_churn(full: bool) -> None:
    """Topology churn under the mobile-sensor convoy workload (DESIGN.md
    Sec. 10). A 1600-slot fleet with a drifting convoy (~3% of edges
    change per frame) streams through one ``StreamingFilter`` with
    per-frame ``GraphDelta``s. Three comparisons, all against the
    from-scratch baseline on the *same* evolved graph:

    * ``churn_incremental_frame`` vs ``churn_full_rebuild_frame`` —
      wall time per frame: churn-corrected restricted kernels + plan
      repair vs full re-partition + full dense refilter.
    * words/frame — restricted-walk accounting vs the full model
      ``order * halo_words`` of a freshly rebuilt plan.
    * ``churn_repair_plan`` — ``repair_partition_plan`` vs
      ``build_partition_plan`` on the post-delta adjacency.

    ``churn_summary`` carries the acceptance bits: parity <= 1e-5 vs the
    dense oracle on every frame, latency/words/repair each < 0.5x the
    baseline at <= 5% churn, and zero churn-kernel retraces over the
    second half of the run (bucket set warm)."""
    from repro.core.chebyshev import cheb_apply_dense
    from repro.core.distributed import repair_partition_plan
    from repro.dynamic import kernel_trace_counts, mobile_sensor_scenario
    from repro.dynamic.delta import apply_delta_inplace

    n_slots, order, n_parts = 1600, 10, 8
    n_frames = 14 if full else 10
    t0 = time.perf_counter()
    sc = mobile_sensor_scenario(
        n_slots, n_frames, mobility="convoy", seed=7,
        cluster_radius=0.07, speed=0.012,
        birth_rate=0.2, death_rate=0.2, bump_radius=0.12)
    gen_s = time.perf_counter() - t0
    g = sc.graph0

    # 1.5x headroom on the AM bound keeps the polynomial certified across
    # every frame (no re-expansion frames in the steady-state numbers).
    lmax0 = 1.5 * float(g.lmax_bound())
    filt = GraphFilter.from_multipliers(
        [multipliers.heat(1.0), lambda x: x / (1.0 + x)],
        order, graph=g, lmax=lmax0)
    lane = StreamingFilter(filt, backend="dense", n_parts=n_parts,
                           max_delta_frac=0.9)
    lane.push(sc.frames[0].signal)  # cold frame (captures the Krylov stack)

    # Host-side evolving reference state for the baselines + oracle.
    adj = np.array(np.asarray(g.adjacency, np.float32))
    lap = np.diag(adj.sum(axis=1)) - adj
    coords = np.array(np.asarray(g.coords))
    plan_prev = build_partition_plan(adj, coords, n_parts)
    coeffs32 = np.asarray(filt.coeffs, np.float32)
    # Warm the dense oracle program once so baseline timings are compiled.
    jax.block_until_ready(
        cheb_apply_dense(jnp.asarray(lap, jnp.float32),
                         sc.frames[0].signal, coeffs32, filt.lmax))

    lat_inc, lat_base, lat_repair, lat_rebuild = [], [], [], []
    words_inc, words_full, modes = [], [], []
    parity = 0.0
    trace_mid = None
    mid = 1 + (len(sc.frames) - 1) // 2
    for i, fr in enumerate(sc.frames[1:], start=1):
        t0 = time.perf_counter()
        res = lane.push(fr.signal, delta=fr.delta)
        lat_inc.append(time.perf_counter() - t0)
        words_inc.append(res.words)
        modes.append(res.mode)

        # Evolve the reference graph, then time the from-scratch baseline
        # on it: full re-partition + full dense refilter.
        apply_delta_inplace(adj, lap, fr.delta)
        if fr.delta.coords is not None:
            coords = np.array(fr.delta.coords)
        t0 = time.perf_counter()
        plan_rep = repair_partition_plan(plan_prev, adj, fr.delta.touched)
        lat_repair.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        plan_new = build_partition_plan(adj, coords, n_parts)
        dt_rebuild = time.perf_counter() - t0
        lat_rebuild.append(dt_rebuild)
        plan_prev = plan_rep
        t0 = time.perf_counter()
        ref = jax.block_until_ready(
            cheb_apply_dense(jnp.asarray(lap, jnp.float32),
                             fr.signal, coeffs32, filt.lmax))
        lat_base.append(dt_rebuild + (time.perf_counter() - t0))
        words_full.append(order * plan_new.halo_words)
        parity = max(parity, float(np.max(np.abs(lane._out - np.asarray(ref)))))
        if i == mid:
            trace_mid = dict(kernel_trace_counts())
    retraces = sum(kernel_trace_counts().values()) - sum(trace_mid.values())

    med = lambda xs: float(np.median(xs))  # noqa: E731
    lat_ratio = med(lat_inc) / med(lat_base)
    words_ratio = float(np.mean(words_inc)) / float(np.mean(words_full))
    rep_ratio = med(lat_repair) / med(lat_rebuild)
    n_churn = sum(1 for m in modes if m == "churn")
    row("churn_incremental_frame", med(lat_inc) * 1e6,
        f"frames={len(modes)};churn_frames={n_churn}"
        f";mean_churn={sc.mean_churn:.4f}"
        f";words_mean={np.mean(words_inc):.0f}"
        f";reexpansions={lane.reexpansions};gen_s={gen_s:.2f}")
    row("churn_full_rebuild_frame", med(lat_base) * 1e6,
        f"words_full_mean={np.mean(words_full):.0f}"
        f";model=order*halo_words(fresh plan)")
    row("churn_repair_plan", med(lat_repair) * 1e6,
        f"rebuild_us={med(lat_rebuild) * 1e6:.1f}"
        f";repair_ratio={rep_ratio:.3f}")
    row("churn_summary", 0.0,
        f"latency_ratio={lat_ratio:.3f};words_ratio={words_ratio:.3f}"
        f";repair_ratio={rep_ratio:.3f};parity={parity:.1e}"
        f";retraces_steady={retraces}"
        f";accept_latency_lt_half={int(lat_ratio < 0.5)}"
        f";accept_words_lt_half={int(words_ratio < 0.5)}"
        f";accept_repair_lt_half={int(rep_ratio < 0.5)}"
        f";accept_parity_le_1e5={int(parity <= 1e-5)}"
        f";accept_churn_le_5pct={int(sc.mean_churn <= 0.05)}"
        f";accept_zero_retraces={int(retraces == 0)}")


# ----------------------------------------------------------- roofline --


def tab_roofline(full: bool) -> None:
    path = Path(__file__).resolve().parents[1] / "experiments" / \
        "dryrun_baseline.json"
    if not path.exists():
        row("tab_roofline", 0.0, "missing(run repro.launch.dryrun --all)")
        return
    records = json.loads(path.read_text())
    done = [r for r in records if "bottleneck" in r]
    by_bn = {}
    for r in done:
        by_bn[r["bottleneck"]] = by_bn.get(r["bottleneck"], 0) + 1
    row("tab_roofline", 0.0,
        f"cells={len(done)};bottlenecks={by_bn}"
        f";skipped={sum(1 for r in records if 'skipped' in r)}"
        f";errors={sum(1 for r in records if 'error' in r)}")


BENCHES = [fig4_cheb_approx, tab_denoising, tab_comm_scaling,
           tab_wavelet_ista, tab_gossip, tab_train, tab_kernel,
           tab_filter_backends, tab_solvers, tab_streaming, tab_engine,
           tab_churn, tab_roofline]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale trial counts (1000-trial denoising)")
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    use_compile_cache()
    print("name,us_per_call,derived")
    for bench in BENCHES:
        if args.only and args.only not in bench.__name__:
            continue
        bench(args.full)


if __name__ == "__main__":
    main()
