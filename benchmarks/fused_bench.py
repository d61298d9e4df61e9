"""Fused vs two-pass D̃-apply, and batched vs looped GW solving.

Run:  PYTHONPATH=src python benchmarks/fused_bench.py [--out BENCH_fused.json]

Emits BENCH_fused.json:
  dtilde_apply:  per (backend, n, p) — fused single-sweep apply_abs_power
                 vs the historical two-pass apply_L + apply_LT, median
                 seconds + speedup.
  batched_solve: B ragged GW problems through ONE entropic_gw_batch call vs
                 a Python loop of entropic_gw (both jit-warm), + the
                 compile-amortization win (cold wall-time of the second
                 batch on fresh shapes in the same bucket).
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmarks.common import random_measure, timeit
from repro.core import GWConfig, entropic_gw, entropic_gw_batch, fgc
from repro.core.grids import Grid1D
from repro.launch.compile_cache import use_compile_cache


def bench_dtilde(ns=(256, 1024, 4096), ps=(1, 2), b=64):
    rows = []
    rng = np.random.default_rng(0)
    for n in ns:
        x = jnp.asarray(rng.normal(size=(n, b)))
        for p in ps:
            for backend in ("scan", "cumsum"):
                fused = jax.jit(lambda v, p=p, be=backend:
                                fgc.apply_abs_power(v, 0, p, be))
                two = jax.jit(lambda v, p=p, be=backend:
                              fgc.apply_L(v, 0, p, be)
                              + fgc.apply_LT(v, 0, p, be))
                t_fused, _ = timeit(fused, x, repeats=5)
                t_two, _ = timeit(two, x, repeats=5)
                rows.append({"backend": backend, "n": n, "p": p, "b": b,
                             "fused_s": t_fused, "two_pass_s": t_two,
                             "speedup": t_two / t_fused})
                print(f"dtilde {backend:6s} n={n:5d} p={p} "
                      f"fused={t_fused*1e6:9.1f}us two-pass={t_two*1e6:9.1f}us"
                      f" speedup={t_two/t_fused:.2f}x", flush=True)
    return rows


def bench_batched(sizes=((96, 128), (128, 96), (80, 112), (128, 128),
                         (64, 100), (112, 80), (100, 64), (96, 96))):
    cfg = GWConfig(eps=2e-3, outer_iters=10, sinkhorn_iters=200,
                   backend="cumsum")
    probs = [(Grid1D(m, 1 / (m - 1), 1), Grid1D(n, 1 / (n - 1), 1),
              random_measure(m, 2 * i), random_measure(n, 2 * i + 1))
             for i, (m, n) in enumerate(sizes)]
    pad = (max(m for m, _ in sizes), max(n for _, n in sizes))

    t_batch, _ = timeit(
        lambda: jax.block_until_ready(
            [r.plan for r in entropic_gw_batch(probs, cfg, pad_to=pad)]),
        repeats=3)

    def looped():
        return [jax.block_until_ready(
            entropic_gw(gx, gy, mu, nu, cfg).plan)
            for gx, gy, mu, nu in probs]

    t_loop, _ = timeit(looped, repeats=3)
    row = {"n_problems": len(sizes), "pad_to": list(pad),
           "batch_s": t_batch, "loop_s": t_loop,
           "speedup": t_loop / t_batch}
    print(f"batched_solve B={len(sizes)} batch={t_batch*1e3:.1f}ms "
          f"loop={t_loop*1e3:.1f}ms speedup={t_loop/t_batch:.2f}x",
          flush=True)
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=str(Path(__file__).resolve().parent.parent
                                         / "BENCH_fused.json"))
    ap.add_argument("--quick", action="store_true",
                    help="small sizes for CI smoke")
    ap.add_argument("--smoke", action="store_true",
                    help="alias for --quick (CI executes the perf path)")
    args = ap.parse_args()
    use_compile_cache()
    if args.quick or args.smoke:
        dt = bench_dtilde(ns=(256, 1024), ps=(1, 2), b=16)
        bs = bench_batched(sizes=((32, 40), (40, 32), (24, 36), (40, 40)))
    else:
        dt = bench_dtilde()
        bs = bench_batched()
    out = {"backend": jax.default_backend(),
           "dtilde_apply": dt, "batched_solve": bs}
    Path(args.out).write_text(json.dumps(out, indent=2) + "\n")
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
