"""Fixed-iteration vs convergence-controlled (adaptive) solving — what does
the tolerance-based driver + ε-annealing buy across ε regimes?

Run:  PYTHONPATH=src python benchmarks/solver_bench.py [--out BENCH_solver.json]
      (--smoke: tiny sizes so CI merely executes the perf path)

Modes compared on identical problems:

  fixed     tol=0: the paper's §4.1 policy — 10 outer × ``sinkhorn_iters``
            inner sweeps, blind (no convergence signal).
  adaptive  tol>0: the shared driver's early stopping + ε-annealing
            (geometric decay from eps_init, warm-started potentials).

Regimes:

  easy      ε=5e-2 — fixed mode burns ~10-20× the sweeps it needs.
  hard      ε=2e-3 (the paper's 1D setting) — fixed mode's 200-sweep inner
            budget is too small: it returns a non-converged plan with no
            signal; annealing both converges AND lands in a better basin
            (lower GW energy).
  mixed     a serving stream with per-request ε spanning easy→hard.  The
            fixed policy must provision every request for the hardest one;
            the adaptive driver stops each problem on its own schedule.
            This is the regime the acceptance claim is about: ≥2× fewer
            total inner iterations at equal-or-better (worst-case)
            marginal error.

Emits BENCH_solver.json: per regime and mode — wall seconds, total inner
Sinkhorn iterations, worst/mean final marginal error, GW values — plus a
summary with the inner-iteration ratio and the acceptance flags.
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
from repro.core import GWConfig, SolveControls, entropic_gw
from repro.core.geometry import PointCloudGeometry
from repro.core.grids import Grid1D, Grid2D
from repro.launch.compile_cache import use_compile_cache


FIXED = dict(outer_iters=10, sinkhorn_iters=200)          # paper §4.1
ADAPTIVE = dict(outer_iters=60, sinkhorn_iters=500,       # caps, not budgets
                tol=1e-4, eps_init=5e-2, anneal_decay=0.5)


def _problems(n, eps_list):
    g = Grid1D(n, 1.0 / (n - 1), 1)
    return [(g, g, random_measure(n, 2 * i), random_measure(n, 2 * i + 1),
             eps) for i, eps in enumerate(eps_list)]


def _run_mode(problems, mode_kwargs):
    """Solve every problem, return wall seconds + per-problem stats.

    ONE jitted solve per mode: ε and the tolerance/schedule ride in a
    `SolveControls` operand (the PR's traced-knobs invariant), so every
    problem in a regime — and every ε in the mixed stream — reuses the same
    executable.
    """
    cfg = GWConfig(**mode_kwargs).static_key()
    gx0, gy0 = problems[0][0], problems[0][1]
    assert all(p[0] is gx0 and p[1] is gy0 for p in problems), \
        "_run_mode jits one solve over the first problem's geometry"
    solve = jax.jit(lambda mu, nu, ctl: entropic_gw(gx0, gy0, mu, nu, cfg,
                                                    controls=ctl))
    inner, errs, values, outers = [], [], [], []
    wall = 0.0
    for (_, _, mu, nu, eps) in problems:
        ctl = SolveControls.make(eps, mode_kwargs.get("tol", 0.0),
                                 mode_kwargs.get("eps_init"),
                                 mode_kwargs.get("anneal_decay", 0.5))
        t, res = timeit(solve, mu, nu, ctl, repeats=3)
        wall += t
        # recompute the marginal gap from the returned plan so fixed
        # (tol=0) and adaptive report the identical metric
        errs.append(float(jnp.abs(res.plan.sum(axis=1) - mu).sum()))
        inner.append(int(res.info.inner_iters))
        outers.append(int(res.info.outer_iters))
        values.append(float(res.value))
    return {"wall_seconds": wall, "total_inner_iters": int(sum(inner)),
            "inner_iters": inner, "outer_iters": outers,
            "max_marginal_err": max(errs), "mean_marginal_err":
                float(np.mean(errs)), "marginal_errs": errs,
            "values": values}


def bench(n, smoke):
    eps_easy, eps_hard = 5e-2, 2e-3
    regimes = {
        "easy": [eps_easy] * (2 if smoke else 4),
        "hard": [eps_hard] * (2 if smoke else 4),
        "mixed": [5e-2, 2e-3] if smoke else [5e-2, 2e-2, 8e-3, 2e-3],
    }
    fixed_kw = dict(FIXED)
    adaptive_kw = dict(ADAPTIVE)
    if smoke:
        fixed_kw.update(sinkhorn_iters=50)
        adaptive_kw.update(outer_iters=20, sinkhorn_iters=100)

    out = {"backend": jax.default_backend(), "n": n,
           "fixed_cfg": fixed_kw, "adaptive_cfg": adaptive_kw,
           "regimes": {}, "summary": {}}
    for name, eps_list in regimes.items():
        probs = _problems(n, eps_list)
        fixed = _run_mode(probs, fixed_kw)
        adaptive = _run_mode(probs, adaptive_kw)
        ratio = fixed["total_inner_iters"] / max(adaptive["total_inner_iters"],
                                                 1)
        err_ok = adaptive["max_marginal_err"] <= fixed["max_marginal_err"]
        out["regimes"][name] = {"eps": eps_list, "fixed": fixed,
                                "adaptive": adaptive}
        out["summary"][name] = {
            "inner_iter_ratio": ratio,
            "adaptive_err_leq_fixed": bool(err_ok),
            "acceptance": bool(ratio >= 2.0 and err_ok),
        }
        print(f"{name:6s} inner {fixed['total_inner_iters']:6d} → "
              f"{adaptive['total_inner_iters']:6d}  ({ratio:4.2f}× fewer)  "
              f"worst err {fixed['max_marginal_err']:.2e} → "
              f"{adaptive['max_marginal_err']:.2e}  "
              f"wall {fixed['wall_seconds']:.3f}s → "
              f"{adaptive['wall_seconds']:.3f}s", flush=True)
    out["acceptance_any_regime"] = any(
        s["acceptance"] for s in out["summary"].values())

    # ---- annealing validation beyond 1D grids (ROADMAP item): Grid2D at
    # the paper's ε=0.004, plus a point cloud and its low-rank factorization
    # at the 1D hard ε.  The claim is qualitative: the fixed budget returns
    # a non-converged plan (err ≫ tol, no signal), annealing converges.
    rng = np.random.default_rng(21)
    n2 = 5 if smoke else 8
    npc = 16 if smoke else 48
    pc = PointCloudGeometry(jnp.asarray(rng.random((npc, 2))))
    cases = [("grid2d", Grid2D(n2, 1.0 / (n2 - 1), 1), n2 * n2, 4e-3),
             ("pointcloud", pc, npc, 2e-3),
             ("lowrank", pc.to_low_rank(), npc, 2e-3)]
    tol = adaptive_kw["tol"]
    out["geometries"] = {}
    for name, geom, npts, eps in cases:
        probs = [(geom, geom, random_measure(npts, 30 + i),
                  random_measure(npts, 40 + i), eps) for i in range(2)]
        fixed = _run_mode(probs, fixed_kw)
        adaptive = _run_mode(probs, adaptive_kw)
        ok = (fixed["max_marginal_err"] > tol
              and adaptive["max_marginal_err"] <= tol)
        out["geometries"][name] = {
            "eps": eps, "n_points": npts, "fixed": fixed,
            "adaptive": adaptive,
            "adaptive_converges_where_fixed_does_not": bool(ok),
        }
        # smoke budgets (20×100) are far below what the hard-ε cases need:
        # smoke only proves the path executes, so don't print/record a
        # convergence verdict CI would misread as a regression
        tag = ("smoke: path-execution only" if smoke
               else ("OK" if ok else "MISS"))
        print(f"{name:10s} ε={eps:.0e}  fixed err "
              f"{fixed['max_marginal_err']:.2e} (no signal) → adaptive err "
              f"{adaptive['max_marginal_err']:.2e} [{tag}]", flush=True)
    out["acceptance_geometries"] = None if smoke else all(
        g["adaptive_converges_where_fixed_does_not"]
        for g in out["geometries"].values())
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=str(Path(__file__).resolve().parent.parent
                                         / "BENCH_solver.json"))
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes: execute the perf path in CI")
    ap.add_argument("--n", type=int, default=None, help="problem size")
    args = ap.parse_args()
    use_compile_cache()
    n = args.n or (24 if args.smoke else 64)
    out = bench(n, args.smoke)
    Path(args.out).write_text(json.dumps(out, indent=2) + "\n")
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
