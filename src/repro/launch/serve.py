"""Serving launcher.

Local mode runs real batched generation through the ServeEngine (smoke
configs on CPU); ``--dryrun`` AOT-compiles the production decode cell.

Examples:
  python -m repro.launch.serve --arch gemma2_2b --smoke --tokens 16
  python -m repro.launch.serve --arch llama3_405b --shape decode_32k --dryrun
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.configs import registry
from repro.models import lm
from repro.models.config import ParallelConfig
from repro.serve import ServeEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="decode_32k")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--dryrun", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()

    if args.dryrun:
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

    cfg = registry.get_smoke(args.arch) if args.smoke \
        else registry.get(args.arch)
    params, _ = lm.init(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(
        cfg=cfg, par=ParallelConfig(attn_impl="naive", remat="none"),
        params=params, s_max=args.prompt_len + args.tokens + 8,
        temperature=args.temperature)
    prompts = np.random.RandomState(0).randint(
        0, cfg.vocab_size, size=(args.batch, args.prompt_len)).astype(np.int32)
    t0 = time.monotonic()
    out = engine.generate(prompts, max_new_tokens=args.tokens)
    dt = time.monotonic() - t0
    print(json.dumps({
        "arch": cfg.name,
        "batch": args.batch,
        "new_tokens": args.tokens,
        "wall_s": round(dt, 2),
        "tokens_per_s": round(args.batch * args.tokens / dt, 1),
        "sample": out[0][:8].tolist(),
    }, indent=1))


if __name__ == "__main__":
    main()
