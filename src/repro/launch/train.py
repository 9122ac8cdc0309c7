"""Training launcher.

Runs real training on the available devices (CPU smoke / small models) or,
with ``--dryrun``, AOT-compiles the production-mesh cell instead (no
allocation). The same ``make_train_step`` drives both paths.

Examples:
  python -m repro.launch.train --arch gemma2_2b --smoke --steps 50
  python -m repro.launch.train --arch llama3_405b --shape train_4k --dryrun
"""

from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax

from repro.checkpoint import CheckpointManager, latest_step, restore
from repro.configs import registry
from repro.data import SyntheticTokenPipeline
from repro.launch.donation import jit_train_step
from repro.models import lm
from repro.models.config import ParallelConfig
from repro.optim import AdamWConfig, init_opt_state
from repro.runtime import run_with_restarts
from repro.runtime.fault import StragglerMonitor
from repro.train import Trainer, make_gossip_train_step, make_train_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config on local devices")
    ap.add_argument("--dryrun", action="store_true",
                    help="AOT-compile the production cell instead")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--grad-sync", default="allreduce",
                    choices=["allreduce", "gossip"])
    ap.add_argument("--gossip-order", type=int, default=None)
    ap.add_argument("--gossip-buckets", type=int, default=4,
                    help="flat gradient buckets for the gossip pipeline")
    ap.add_argument("--gossip-payload", default=None,
                    choices=[None, "bfloat16", "float32"],
                    help="wire dtype of gossip exchanges (math stays f32)")
    ap.add_argument("--gossip-truncate", type=int, default=0,
                    help="drop the last r gossip rounds (bounded staleness)")
    ap.add_argument("--no-overlap", action="store_true",
                    help="serial post-backward gossip (benchmark baseline)")
    ap.add_argument("--no-donate", action="store_true",
                    help="keep pre-step params/opt_state buffers alive")
    args = ap.parse_args()

    if args.dryrun:
        # delegate to the dry-run driver (forces 512 host devices, so it
        # must own the process).
        import os
        import subprocess
        import sys
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", args.arch, "--shape", args.shape]
        if args.multi_pod:
            cmd.append("--multi-pod")
        # The dry-run forces 512 host devices: keep it off the
        # accelerator this process may already hold.
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        raise SystemExit(subprocess.call(cmd, env=env))

    cfg = registry.get_smoke(args.arch) if args.smoke else registry.get(args.arch)
    par = ParallelConfig(attn_impl="naive", remat="none",
                         grad_sync=args.grad_sync,
                         gossip_order=args.gossip_order,
                         gossip_buckets=args.gossip_buckets,
                         gossip_overlap=not args.no_overlap,
                         gossip_payload_dtype=args.gossip_payload,
                         gossip_truncate=args.gossip_truncate,
                         fsdp=args.grad_sync != "gossip")
    optc = AdamWConfig(peak_lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                       total_steps=args.steps)
    pipe = SyntheticTokenPipeline(cfg.vocab_size, args.seq, args.batch)
    mgr = CheckpointManager(args.ckpt_dir, keep=3)
    if args.grad_sync == "gossip":
        # Decentralized DP: replicate params, gossip the gradients over a
        # 1-D data mesh covering all local devices.
        from repro.core.compat import make_mesh
        n_dev = len(jax.devices())
        mesh = make_mesh((n_dev,), ("data",))
        step_fn = jit_train_step(
            make_gossip_train_step(cfg, par, optc, None, mesh),
            donate=not args.no_donate)
    else:
        step_fn = jit_train_step(make_train_step(cfg, par, optc),
                                 donate=not args.no_donate)

    def make_trainer(start_step: int) -> Trainer:
        params, _ = lm.init(jax.random.PRNGKey(0), cfg)
        opt = init_opt_state(params, optc)
        if start_step > 0:
            snap = restore(args.ckpt_dir, start_step,
                           {"params": params, "opt": opt})
            params, opt = snap["params"], snap["opt"]
            print(f"resumed from step {start_step}")
        return Trainer(train_step=step_fn, pipeline=pipe, ckpt=mgr,
                       params=params, opt_state=opt,
                       ckpt_every=args.ckpt_every,
                       straggler_monitor=StragglerMonitor())

    result = run_with_restarts(
        make_trainer, args.steps,
        latest_step_fn=lambda: latest_step(args.ckpt_dir))
    losses = result["losses"]
    print(json.dumps({
        "arch": cfg.name, "steps": result["final_step"],
        "loss_first5": round(float(sum(losses[:5]) / max(len(losses[:5]), 1)), 4),
        "loss_last5": round(float(sum(losses[-5:]) / max(len(losses[-5:]), 1)), 4),
        "wall_s": round(result["wall_s"], 1),
        "restarts": result["restarts"],
    }, indent=1))


if __name__ == "__main__":
    main()
