"""Training driver: data -> train_step -> checkpoint/restart, fault-tolerant.

Runs for real on any mesh that fits the local devices (examples use a tiny
config on CPU); on a pod the same driver runs under the production mesh.
Integrates the paper-derived control plane:

  * ClusterCoordinator.checkpoint_fence (XF barrier) before every save;
  * straggler detection via heartbeats (single-writer words);
  * auto-resume from the latest committed checkpoint (elastic restarts re-
    enter here after mesh re-formation — see train/elastic.py).

Usage:
  python -m repro.launch.train --arch qwen3-14b --smoke --steps 100 \
      --ckpt-dir /tmp/ckpt [--resume]
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.core.coordinator import ClusterCoordinator
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.train import optimizer as opt
from repro.train.checkpoint import CheckpointManager
from repro.train.data import Prefetcher, SyntheticLM
from repro.train.train_loop import make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    if cfg.is_encdec or cfg.frontend is not None:
        raise SystemExit("train.py drives token-LM archs; use examples/ for "
                         "stub-frontend families")

    model = build_model(cfg)
    ocfg = opt.AdamWConfig(peak_lr=args.lr, warmup_steps=max(args.steps // 20, 5),
                           total_steps=args.steps)
    step_fn = jax.jit(make_train_step(
        model, ocfg, num_microbatches=args.microbatches, remat=True))

    params = model.init(jax.random.PRNGKey(args.seed))
    state = opt.init(ocfg, params)
    start_step = 0

    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    coord = ClusterCoordinator(world=1, barrier_timeout_s=60)
    if ckpt and args.resume:
        latest = ckpt.latest_step()
        if latest is not None:
            tree = ckpt.restore(latest, {"params": params,
                                         "m": state.m, "v": state.v,
                                         "count": state.count})
            params = tree["params"]
            state = opt.AdamWState(count=tree["count"], m=tree["m"],
                                   v=tree["v"])
            start_step = latest + 1
            print(f"[train] resumed from step {latest}")

    ds = Prefetcher(SyntheticLM(cfg.vocab_size, args.batch, args.seq,
                                seed=args.seed, start_step=start_step))
    t0 = time.time()
    tokens_done = 0
    try:
        for step in range(start_step, args.steps):
            raw = next(ds)
            batch = {k: jnp.asarray(v) for k, v in raw.items()}
            params, state, metrics = step_fn(params, state, batch)
            coord.heartbeat(0, step)
            tokens_done += args.batch * args.seq
            if step % args.log_every == 0 or step == args.steps - 1:
                loss = float(metrics["loss"])
                tps = tokens_done / max(time.time() - t0, 1e-6)
                print(f"[train] step {step:5d} loss {loss:.4f} "
                      f"lr {float(metrics['lr']):.2e} "
                      f"gnorm {float(metrics['grad_norm']):.2f} "
                      f"tok/s {tps:,.0f}")
            if ckpt and (step + 1) % args.ckpt_every == 0:
                assert coord.checkpoint_fence(0)
                ckpt.save_async(step, {"params": params, "m": state.m,
                                       "v": state.v, "count": state.count})
        if ckpt:
            assert coord.checkpoint_fence(0)
            ckpt.save(args.steps - 1, {"params": params, "m": state.m,
                                       "v": state.v, "count": state.count})
            ckpt.wait()
    finally:
        ds.close()
    print(f"[train] done: {args.steps - start_step} steps in "
          f"{time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
