"""Full-plan vs factored-plan GW: where does O(N(r+d)) beat O(MN)?

Run:  PYTHONPATH=src python benchmarks/lowrank_bench.py [--out BENCH_lowrank.json]
      (--smoke: tiny sizes so CI merely executes both representations
      and both factored backends)

Setup: squared-Euclidean point clouds, BOTH plans given the identical
factored cost (`PointCloudGeometry.to_low_rank()`, exact rank d+2) so the
plan representation is the ONLY axis — the full path still builds (M,N)
gradients and runs (M,N) Sinkhorn; the factored path never materializes an
(M,N) array.  Iteration counts are matched exactly (fixed mode, same outer
and inner caps), so wall-clock compares the same number of mirror steps.

The factored plan carries a second axis, ``lowrank_backend``:

  * ``xla``    — the reference lowering; the number the acceptance flags
                 judge, on any host.
  * ``pallas`` — the fused Dykstra/Gram kernels (`repro.kernels.lr_step`).
                 Off-TPU these run in INTERPRET mode, which executes the
                 kernel's blocked program step by step in Python — the
                 timing is honest about that (orders of magnitude slower
                 than both XLA and a real TPU) and is reported as
                 ``interpreted: true``, NOT as the kernel's device speed.
                 On a TPU host the same case reports compiled-kernel time.

Each case runs in a SUBPROCESS (``--case plan:n:backend``) so peak memory
is a real per-case ``ru_maxrss``, not an accumulation across cases, and so
the 100k/1M-point full-plan cases can be declared impossible (an (M,N) f64
plan alone is ~80 GB at N=100k, ~8 TB at N=1M) without trying to allocate
them.  The N=1M factored case is the paper-scale headline: one device,
factors only, peak RSS a few hundred MB.

Emits BENCH_lowrank.json with per-case wall-clock + peak RSS and the
acceptance flags: the factored plan must win BOTH wall-clock and peak
memory at N ≥ 10k (crossover: at 1k the dense path's fused (M,N) kernels
are fine; the factored path's win is asymptotic, not universal).
"""
from __future__ import annotations

import argparse
import json
import os
import resource
import subprocess
import sys
import time
from pathlib import Path

_REPO = Path(__file__).resolve().parent.parent

FULL_SIZES = [1_000, 10_000]        # both plans, matched iterations
LR_ONLY_SIZES = [100_000, 1_000_000]  # factored only: dense plan cannot fit
PALLAS_SIZES = [1_000]              # fused kernels; interpret-mode off-TPU
SMOKE_SIZES = [256, 1_024]
OUTER, INNER, CHUNK, RANK = 2, 10, 5, 8


def _on_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"


def _run_case(plan: str, n: int, backend: str) -> dict:
    import jax

    from repro.launch.compile_cache import use_compile_cache

    jax.config.update("jax_enable_x64", True)
    use_compile_cache()
    import jax.numpy as jnp
    import numpy as np

    from repro.core import GWConfig, entropic_gw
    from repro.core.geometry import PointCloudGeometry

    r = np.random.default_rng(0)
    gx = PointCloudGeometry(jnp.asarray(r.normal(size=(n, 3)))).to_low_rank()
    gy = PointCloudGeometry(jnp.asarray(r.normal(size=(n, 3)))).to_low_rank()
    mu = jnp.ones(n) / n
    nu = jnp.ones(n) / n
    kw = {} if plan == "full" else {"lowrank_backend": backend}
    cfg = GWConfig(eps=5e-2, outer_iters=OUTER, sinkhorn_iters=INNER,
                   sinkhorn_chunk=CHUNK, plan=plan, plan_rank=RANK, **kw)

    fn = jax.jit(lambda mu, nu: entropic_gw(gx, gy, mu, nu, cfg))
    res = fn(mu, nu)                      # compile + first run
    jax.block_until_ready(res.value)
    t0 = time.perf_counter()
    res = fn(mu, nu)
    jax.block_until_ready(res.value)
    wall = time.perf_counter() - t0
    out = {
        "plan": plan, "n": n, "backend": backend, "wall_s": wall,
        "peak_rss_mb": resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        / 1024.0,
        "value": float(res.value),
        "marginal_err": float(res.marginal_err),
    }
    if backend == "pallas" and not _on_tpu():
        out["interpreted"] = True     # honest: NOT the kernel's device speed
    return out


def _spawn_case(plan: str, n: int, backend: str = "-") -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(_REPO / "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    out = subprocess.run(
        [sys.executable, __file__, "--case", f"{plan}:{n}:{backend}"],
        capture_output=True, text=True, check=True, cwd=_REPO, env=env)
    return json.loads(out.stdout.strip().splitlines()[-1])


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_lowrank.json")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--case", default=None, help="internal: run one case "
                    "in-process and print its JSON (plan:n:backend)")
    args = ap.parse_args()

    if args.case:
        plan, n, backend = args.case.split(":")
        print(json.dumps(_run_case(plan, int(n), backend)))
        return

    def _go(plan, n, backend="-"):
        tag = plan if backend == "-" else f"{plan}/{backend}"
        print(f"[lowrank_bench] {tag:15s} n={n} ...", flush=True)
        cases.append(_spawn_case(plan, n, backend))
        note = " (interpret)" if cases[-1].get("interpreted") else ""
        print(f"    {cases[-1]['wall_s']:.3f}s "
              f"{cases[-1]['peak_rss_mb']:.0f} MB{note}", flush=True)

    sizes = SMOKE_SIZES if args.smoke else FULL_SIZES
    cases: list[dict] = []
    for n in sizes:
        _go("full", n)
        _go("lowrank", n, "xla")
    # fused-kernel axis: small N in smoke (CI just executes it); off-TPU the
    # interpret-mode wall-clock is reported but never judged
    for n in (SMOKE_SIZES[:1] if args.smoke else PALLAS_SIZES):
        _go("lowrank", n, "pallas")
    if not args.smoke:
        for n in LR_ONLY_SIZES:
            tb = 80e9 * (n / 100_000) ** 2 / 1e12
            cases.append({"plan": "full", "n": n, "backend": "-", "skipped":
                          f"dense (M,N) f64 plan alone is ~{tb:.2g} TB"
                          if tb >= 1 else
                          "dense (M,N) f64 plan alone is ~80 GB at N=100k"})
            _go("lowrank", n, "xla")

    def _pick(plan, n, backend="-"):
        for c in cases:
            if (c["plan"] == plan and c["n"] == n and "wall_s" in c
                    and c.get("backend", "-") == backend):
                return c
        return None

    crossover_n = max(sizes)
    f, l = _pick("full", crossover_n), _pick("lowrank", crossover_n, "xla")
    million = _pick("lowrank", 1_000_000, "xla")
    acceptance = {
        "crossover_n": crossover_n,
        "lowrank_wins_wall": bool(f and l and l["wall_s"] < f["wall_s"]),
        "lowrank_wins_mem": bool(
            f and l and l["peak_rss_mb"] < f["peak_rss_mb"]),
        "million_point_single_device": bool(million is not None),
    }
    report = {"mode": "smoke" if args.smoke else "full",
              "iters": {"outer": OUTER, "sinkhorn": INNER, "rank": RANK},
              "cases": cases, "acceptance": acceptance}
    Path(args.out).write_text(json.dumps(report, indent=2) + "\n")
    print(json.dumps(acceptance, indent=2))


if __name__ == "__main__":
    main()
