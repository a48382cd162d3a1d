"""Fault-tolerant training driver.

Single entry point for real runs and for the CPU-scale examples:

  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --smoke \
      --steps 200 --batch 8 --seq 128

Fault tolerance:
* checkpoint every ``--ckpt-every`` steps (async, atomic, rotating);
* on start, auto-resume from the latest checkpoint (params + optimizer + step);
* deterministic data: batch i depends only on (seed, i), so a restart replays
  the exact stream;
* ``--simulate-failure N`` kills the process at step N (exit 17); the outer
  supervisor loop (``--supervise``) restarts it, proving end-to-end
  checkpoint/restart.  On a real cluster the supervisor is the job scheduler;
  the in-process logic is identical.
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def train(args) -> int:
    from repro.checkpoint import CheckpointManager
    from repro.configs import get_config, get_smoke_config
    from repro.data import DataConfig, TokenStream
    from repro.models import build_model
    from repro.optim import AdamWConfig, adamw_init
    from repro.training import make_train_step
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    opt_cfg = AdamWConfig(lr=args.lr, total_steps=args.steps,
                          warmup_steps=max(args.steps // 20, 5))
    step_fn = jax.jit(make_train_step(model, opt_cfg,
                                      microbatches=args.microbatches),
                      donate_argnums=(0, 1))

    data = TokenStream(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                  global_batch=args.batch, seed=args.seed))

    ckpt = CheckpointManager(args.ckpt_dir, keep=2)
    params = model.init_params(jax.random.key(args.seed))
    opt_state = adamw_init(params)
    start_step = 0
    state_tmpl = {"params": params, "opt": opt_state}
    restored, meta = ckpt.restore_latest(state_tmpl)
    if restored is not None:
        params, opt_state = restored["params"], restored["opt"]
        start_step = int(meta.get("step", 0))
        print(f"[train] resumed from step {start_step}", flush=True)

    losses = []
    t0 = time.time()
    for step in range(start_step, args.steps):
        if args.simulate_failure >= 0 and step == args.simulate_failure:
            print(f"[train] SIMULATED FAILURE at step {step}", flush=True)
            os._exit(17)
        batch = data.batch(step)
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        loss = float(metrics["loss"])
        losses.append(loss)
        if step % args.log_every == 0 or step == args.steps - 1:
            dt = time.time() - t0
            print(f"[train] step {step} loss {loss:.4f} "
                  f"lr {float(metrics['lr']):.2e} gnorm {float(metrics['grad_norm']):.2f} "
                  f"({dt:.1f}s)", flush=True)
        if step > start_step and step % args.ckpt_every == 0:
            ckpt.save({"params": params, "opt": opt_state}, step=step + 1)
    ckpt.save({"params": params, "opt": opt_state}, step=args.steps)
    ckpt.wait()
    first = np.mean(losses[:5]) if len(losses) >= 5 else losses[0]
    last = np.mean(losses[-5:])
    print(f"[train] done: loss {first:.4f} -> {last:.4f} "
          f"({'improved' if last < first else 'NOT improved'})", flush=True)
    return 0


def supervise(argv: list[str], max_restarts: int = 5) -> int:
    """Heartbeat supervisor: restart the training subprocess on failure.

    The parent never starts a JAX backend: a process that has touched the
    accelerator holds it, and its children could then not train on it."""
    for attempt in range(max_restarts + 1):
        child = [sys.executable, "-m", "repro.launch.train"] + argv
        print(f"[supervisor] launch attempt {attempt}: {' '.join(child)}", flush=True)
        p = subprocess.run(child, env={**os.environ, "REPRO_SUPERVISED": "1"})
        if p.returncode == 0:
            print("[supervisor] run completed", flush=True)
            return 0
        print(f"[supervisor] child exited rc={p.returncode}; restarting "
              f"(node-failure recovery path)", flush=True)
        # after the first restart, stop injecting failures
        if "--simulate-failure" in argv:
            i = argv.index("--simulate-failure")
            argv = argv[:i] + argv[i + 2:]
    return 1


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="results/ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--simulate-failure", type=int, default=-1)
    ap.add_argument("--supervise", action="store_true")
    args, rest = ap.parse_known_args()

    if args.supervise and not os.environ.get("REPRO_SUPERVISED"):
        argv = [a for a in sys.argv[1:] if a != "--supervise"]
        sys.exit(supervise(argv))
    sys.exit(train(args))


if __name__ == "__main__":
    main()
