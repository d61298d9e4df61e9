"""Kernel-level benchmarks.

Two surfaces:

  * ``run(report)`` — the FGC operator-backend micro rows used by
    ``benchmarks/run.py`` (paper §3 primitive), unchanged.
  * a standalone CLI emitting ``BENCH_kernels.json``:

      PYTHONPATH=src python benchmarks/kernels_bench.py [--smoke] \
          [--out BENCH_kernels.json]

    ``sinkhorn_sweep``: fused Pallas half-step sweeps vs the XLA logsumexp
    scans at M = N ∈ {256, 1024, 4096} (``--smoke``: {256, 512}), same
    ``sinkhorn_log`` entry point, both jit-warm.  ``solver_delta``: the
    end-to-end adaptive GW solve (ε-annealing, tol>0 — the serving path's
    shape) under each backend.

    Off-TPU the Pallas kernels run in INTERPRET mode — a correctness path,
    not a performance path — so CPU numbers show the fused path *losing*;
    that is expected and recorded (``pallas_mode``).  The fused kernel's
    win condition is TPU: no (M,N) temporaries per half-step (3 fewer
    HBM-round-trips at f32) and compiled execution; roofline notes live in
    EXPERIMENTS.md §Perf.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
from pathlib import Path

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmarks.common import random_measure, timeit
from repro.core import fgc
from repro.core import sinkhorn as sk
from repro.core.grids import Grid1D
from repro.core.gw import GWConfig, entropic_gw
from repro.launch.compile_cache import use_compile_cache


def run(report):
    r = np.random.default_rng(0)
    for n in (512, 2048, 8192):
        x = jnp.asarray(r.normal(size=(n, 128)), jnp.float32)
        for be in ("scan", "cumsum", "blocked", "dense"):
            fn = jax.jit(functools.partial(
                fgc.apply_abs_power, axis=0, power=2, backend=be))
            t, _ = timeit(fn, x)
            report.row("kernel_fgc_apply", n=n, backend=be, seconds=t,
                       gelem_per_s=n * 128 / t / 1e9)


#: largest size the INTERPRETER (off-TPU) pallas path is asked to time —
#: interpret walks the 128×128 grid cells sequentially and is intractable
#: at 4096² on CPU; those rows record pallas_s=null off-TPU (the XLA side
#: still sweeps every size, and TPU runs sweep both sides everywhere)
INTERPRET_PALLAS_CAP = 1024


def bench_sinkhorn_sweep(sizes=(256, 1024, 4096), iters=10, eps=5e-3,
                         repeats=3):
    """Fused kernel sweeps vs XLA scans through the SAME `sinkhorn_log`
    entry point (f32 — the TPU kernel dtype)."""
    rows = []
    interpret = jax.default_backend() != "tpu"
    rng = np.random.default_rng(0)
    for n in sizes:
        cost = jnp.asarray(rng.random((n, n)), jnp.float32)
        mu = random_measure(n, 1).astype(jnp.float32)
        nu = random_measure(n, 2).astype(jnp.float32)
        times = {}
        backends = ["xla"]
        if not (interpret and n > INTERPRET_PALLAS_CAP):
            backends.append("pallas")
        for be in backends:
            fn = jax.jit(functools.partial(
                sk.sinkhorn_log, iters=iters, backend=be))
            t, _ = timeit(lambda: jax.block_until_ready(
                fn(cost, mu, nu, jnp.float32(eps))[1]), repeats=repeats)
            times[be] = t
        pallas_s = times.get("pallas")
        rows.append({"m": n, "n": n, "iters": iters, "eps": eps,
                     "xla_s": times["xla"], "pallas_s": pallas_s,
                     "speedup": (times["xla"] / pallas_s
                                 if pallas_s else None)})
        msg = (f"pallas={pallas_s*1e3:9.1f}ms "
               f"speedup={times['xla']/pallas_s:.2f}x" if pallas_s
               else "pallas=skipped (interpret cap)")
        print(f"sinkhorn_sweep n={n:5d} iters={iters} "
              f"xla={times['xla']*1e3:9.1f}ms " + msg, flush=True)
    return rows


def bench_solver_delta(n=96, repeats=3):
    """End-to-end adaptive GW (ε-annealing + early stop — the serving
    path's program shape) under each Sinkhorn backend."""
    gx = Grid1D(n, 1 / (n - 1), 1)
    mu, nu = random_measure(n, 3), random_measure(n, 4)
    base = GWConfig(eps=5e-3, outer_iters=12, sinkhorn_iters=100, tol=1e-6,
                    eps_init=0.05, anneal_decay=0.5)
    out = {"n": n}
    import dataclasses
    for be in ("xla", "pallas"):
        cfg = dataclasses.replace(base, sinkhorn_backend=be)
        t, res = timeit(lambda cfg=cfg: jax.block_until_ready(
            entropic_gw(gx, gx, mu, nu, cfg).plan), repeats=repeats)
        out[f"{be}_s"] = t
    out["speedup"] = out["xla_s"] / out["pallas_s"]
    print(f"solver_delta n={n} xla={out['xla_s']*1e3:.1f}ms "
          f"pallas={out['pallas_s']*1e3:.1f}ms "
          f"speedup={out['speedup']:.2f}x", flush=True)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=str(Path(__file__).resolve().parent
                                         .parent / "BENCH_kernels.json"))
    ap.add_argument("--quick", action="store_true",
                    help="small sizes for CI smoke")
    ap.add_argument("--smoke", action="store_true",
                    help="alias for --quick (CI executes the perf path)")
    args = ap.parse_args()
    use_compile_cache()
    if args.quick or args.smoke:
        sweep = bench_sinkhorn_sweep(sizes=(256, 512), iters=4, repeats=2)
        delta = bench_solver_delta(n=48, repeats=2)
    else:
        sweep = bench_sinkhorn_sweep()
        delta = bench_solver_delta()
    out = {"backend": jax.default_backend(),
           "pallas_mode": ("compiled" if jax.default_backend() == "tpu"
                           else "interpret"),
           "sinkhorn_sweep": sweep, "solver_delta": delta}
    Path(args.out).write_text(json.dumps(out, indent=2) + "\n")
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
