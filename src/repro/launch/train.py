"""End-to-end training driver.

Examples:
  # ~100M-param smollm-family model, a few hundred steps on CPU/1 device:
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --smoke \\
      --steps 300 --global-batch 8 --seq 256
  # resume after a crash/preemption (picks up latest checkpoint):
  PYTHONPATH=src python -m repro.launch.train ... --ckpt-dir /tmp/run1
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.checkpoint.manager import CheckpointManager, \
    install_preemption_handler
from repro.data import pipeline
from repro.distributed import sharding
from repro.distributed.fault_tolerance import Heartbeat
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import local_mesh
from repro.train import loop as train_loop
from repro.train import optimizer as optim


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--gw-align-weight", type=float, default=0.0,
                    help=">0 adds the FGC-FGW sequence-alignment loss "
                         "against batch['teacher_h']")
    ap.add_argument("--data", default="synthetic",
                    choices=["synthetic", "memmap"])
    ap.add_argument("--data-path", default=None)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = (configs.get_smoke(args.arch) if args.smoke
           else configs.get(args.arch))
    # CPU runs want f32 compute
    if jax.default_backend() == "cpu":
        cfg = dataclasses.replace(cfg, dtype="float32")

    ocfg = optim.OptimizerConfig(lr=args.lr, warmup_steps=args.warmup,
                                 total_steps=args.steps,
                                 compress_grads=args.compress_grads)
    tcfg = train_loop.TrainConfig(microbatches=args.microbatches,
                                  remat=False,
                                  gw_align_weight=args.gw_align_weight,
                                  optimizer=ocfg)
    dcfg = pipeline.DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                               global_batch=args.global_batch,
                               seed=args.seed, kind=args.data,
                               path=args.data_path)
    data = pipeline.make_dataset(dcfg)

    state = train_loop.init_state(jax.random.PRNGKey(args.seed), cfg, tcfg)
    n_params = sum(x.size for x in jax.tree.leaves(state["params"]))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M "
          f"tokens/step={args.global_batch * args.seq}")

    manager = None
    start_step = 0
    if args.ckpt_dir:
        manager = CheckpointManager(args.ckpt_dir, keep=3)
        install_preemption_handler(manager, lambda: state,
                                   lambda: int(state["step"]))
        latest = manager.latest_step()
        if latest is not None:
            state = manager.restore(state, latest)
            start_step = int(state["step"])
            print(f"resumed from checkpoint step {start_step}")
        hb = Heartbeat(args.ckpt_dir + "/heartbeats", host_id=0)
    else:
        hb = None

    step_fn = jax.jit(
        lambda s, b: train_loop.train_step(s, b, cfg, tcfg),
        donate_argnums=(0,))

    t0 = time.time()
    for step in range(start_step, args.steps):
        batch = {k: jnp.asarray(v) for k, v in data.batch(step).items()}
        state, metrics = step_fn(state, batch)
        if hb:
            hb.beat(step)
        if step % args.log_every == 0 or step == args.steps - 1:
            m = {k: float(v) for k, v in metrics.items()}
            dt = time.time() - t0
            tps = (step - start_step + 1) * args.global_batch * args.seq / dt
            print(f"step {step:5d} loss={m['loss']:.4f} ce={m['ce']:.4f} "
                  f"gnorm={m['grad_norm']:.3f} lr={m['lr']:.2e} "
                  f"tok/s={tps:.0f}", flush=True)
        if manager and args.ckpt_every and step and \
                step % args.ckpt_every == 0:
            manager.save_async(step, state)
    if manager:
        manager.save(args.steps, state)
        manager.wait()
    return state


if __name__ == "__main__":
    main()
