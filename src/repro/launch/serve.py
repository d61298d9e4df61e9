"""Batched serving drivers.

LM generation (the original driver):

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --smoke \\
      --batch 4 --prompt-len 8 --max-new 32

GW serving — a standing event loop over a synthetic mixed-difficulty
request stream, through `GWEngine.serve` (admission, dispatch, and harvest
interleaved; pipelined across buckets; plan cache enabled):

  PYTHONPATH=src python -m repro.launch.serve --gw --requests 24 \\
      --repeat-frac 0.5 --cache-capacity 64

``run_event_loop`` (re-exported from `repro.serve.engine`) is the library
surface: feed any iterable of problems to an engine and collect results as
they complete.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time

import jax
import numpy as np

from repro import configs
from repro.checkpoint.manager import CheckpointManager
from repro.launch.compile_cache import use_compile_cache
from repro.models import lm
from repro.serve.engine import (Engine, GWEngine, GWServeConfig, ServeConfig,
                                run_event_loop)

__all__ = ["main", "run_event_loop", "gw_main"]


def _gw_stream(n_requests: int, repeat_frac: float, seed: int):
    """A synthetic serving stream: mixed-size point-cloud GW problems, a
    ``repeat_frac`` fraction of them exact repeats of earlier requests —
    the traffic shape the plan cache exists for."""
    from repro.core.geometry import PointCloudGeometry

    rng = np.random.default_rng(seed)
    sizes = [(12, 16), (16, 12), (24, 24), (8, 20)]
    seen: list[tuple] = []
    for i in range(n_requests):
        if seen and rng.random() < repeat_frac:
            yield seen[rng.integers(len(seen))]
            continue
        m, n = sizes[int(rng.integers(len(sizes)))]
        mu = rng.uniform(0.5, 1.5, m)
        nu = rng.uniform(0.5, 1.5, n)
        prob = (PointCloudGeometry(jax.numpy.asarray(
                    rng.normal(size=(m, 3)), jax.numpy.float32)),
                PointCloudGeometry(jax.numpy.asarray(
                    rng.normal(size=(n, 3)), jax.numpy.float32)),
                jax.numpy.asarray(mu / mu.sum()),
                jax.numpy.asarray(nu / nu.sum()))
        seen.append(prob)
        yield prob


def gw_main(args) -> int:
    """Drive `GWEngine.serve` over the synthetic stream and report the
    pipeline/cache telemetry the engine collected.  Returns the exit code:
    1 when any bucket failed (the engine isolates a failing bucket and
    serves the rest, so a partial result alone would hide the failure)."""
    from repro.core.gw import GWConfig

    solver = GWConfig(eps=2e-1, outer_iters=60, sinkhorn_iters=200,
                      sinkhorn_chunk=25, backend="dense", eps_init=1.0,
                      anneal_decay=0.7)
    engine = GWEngine(GWServeConfig(
        solver=solver, tol=5e-4, max_batch=args.batch, size_bucket=16,
        scheduler="pipeline", max_inflight_buckets=args.inflight,
        cache_capacity=args.cache_capacity, cache_near_tol=args.near_tol,
        cache_profile_tol=args.profile_tol, service=args.service))
    t0 = time.time()
    done = run_event_loop(
        engine, _gw_stream(args.requests, args.repeat_frac, args.seed),
        on_result=lambda rid, res: print(
            f"request {rid}: value={float(res.value):.6f} "
            f"outer={int(res.info.outer_iters)} "
            f"converged={bool(res.info.converged)}"))
    dt = time.time() - t0
    s = engine.stats
    print(f"{len(done)} results in {dt:.2f}s "
          f"({len(done) / max(dt, 1e-9):.1f} req/s)")
    print(f"dispatches={s['dispatches']} depth={s['dispatch_depth']} "
          f"device_idle={s['device_idle_s']:.3f}s "
          f"cache hits/warm/miss={s['cache_hits']}/"
          f"{s['cache_warm_starts']}/{s['cache_misses']} "
          f"(profile={s['cache_profile_hits']}) "
          f"sliced_answers={s['sliced_answers']}")
    for key, exc in engine.last_errors:
        print(f"bucket {key} failed: {exc!r}", file=sys.stderr)
    return 1 if engine.last_errors else 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--gw", action="store_true",
                    help="serve a synthetic GW request stream instead of LM")
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--ckpt-dir", default=None,
                    help="restore trained params instead of random init")
    ap.add_argument("--seed", type=int, default=0)
    # GW event-loop knobs
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--repeat-frac", type=float, default=0.5)
    ap.add_argument("--inflight", type=int, default=2)
    ap.add_argument("--cache-capacity", type=int, default=64)
    ap.add_argument("--near-tol", type=float, default=1e-6)
    ap.add_argument("--profile-tol", type=float, default=0.0,
                    help="sliced-profile second cache stage tolerance "
                         "(0 disables; catches rotated/re-indexed repeats)")
    ap.add_argument("--service", default="exact",
                    choices=["exact", "sliced", "refine"],
                    help="answer class: full solve, O(N log N) sliced "
                         "estimate, or sliced-then-refined")
    args = ap.parse_args(argv)
    use_compile_cache()

    if args.gw:
        return gw_main(args)

    cfg = (configs.get_smoke(args.arch) if args.smoke
           else configs.get(args.arch))
    if jax.default_backend() == "cpu":
        cfg = dataclasses.replace(cfg, dtype="float32")
    params = lm.init_params(jax.random.PRNGKey(args.seed), cfg)
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir)
        state_like = {"params": params}
        params = mgr.restore(state_like)["params"]
        print(f"restored params from step {mgr.latest_step()}")

    engine = Engine(params, cfg,
                    ServeConfig(max_len=args.max_len, batch_size=args.batch,
                                temperature=args.temperature))
    rng = np.random.default_rng(args.seed)
    prompts = rng.integers(0, cfg.vocab_size,
                           (args.batch, args.prompt_len)).astype(np.int32)
    t0 = time.time()
    out = engine.generate(prompts, args.max_new)
    dt = time.time() - t0
    for i, row in enumerate(out):
        print(f"request {i}: {row.tolist()}")
    print(f"{args.batch * args.max_new} tokens in {dt:.2f}s "
          f"({args.batch * args.max_new / dt:.1f} tok/s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
