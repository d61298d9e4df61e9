"""Run the GW main path once on one TPU chip and check what comes out.

    python chip_smoke.py [--seed 0]

Everything runs in this one process, because a chip belongs to one process
at a time.  It runs in float32 with x64 off and every warning raised as an
error, so a float64 input that a kernel wrapper would cast down is fatal.
The phases, each through the entry points a user calls:

  dense     `entropic_gw` on two 128×128 grids, N = M = 16,384 (paper §4.2):
            k = 1, ε = 4e-3 annealed from 5e-2, FGC backend "cumsum",
            Sinkhorn backend "auto" (the Pallas half-step kernels).
  served    `GWEngine` with the pipeline scheduler and a plan cache:
            12 point-cloud requests in R³ with N ∈ {1024, 2048, 4096},
            4 of them exact repeats.
  factored  the README's million-point recipe: N = 10⁶ points in R³,
            plan="lowrank", rank 16, ε = 5e-2, lowrank_backend="auto".
  gradient  `jax.grad` of the `entropic_gw` value with respect to μ on
            N = 4096 point clouds: the implicit VJP behind the Pallas
            forward solve.

Each phase prints its compile and run seconds apart, its `ConvergenceInfo`,
and every checked error beside its bound.  These are smoke timings, not
measurements: one run, with compilation excluded only where it is printed
apart.  The last line of standard output is one JSON object naming the
device.  The script exits non-zero, without that line, when JAX finds no
TPU, when a phase raises, or when a check misses its bound.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import traceback
import warnings
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

DENSE_SIDE = 128                   # Grid2D 128×128 → N = 16,384
SERVED_SIZES = (1024, 2048, 4096, 1024, 2048, 4096, 1024, 2048)
SERVED_REPEATS = (0, 2, 4, 7)      # indices into the unique requests
LR_N, LR_RANK = 1_000_000, 16
GRAD_N = 4096


def _timed(fn, *args):
    """(compiled output, compile seconds, run seconds) of ``jit(fn)``.

    Arrays go in as arguments, never as closures: a closed-over array is a
    constant of the executable, which XLA may fold into padded copies
    hundreds of MB large."""
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    t1 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return out, t1 - t0, time.perf_counter() - t1


def _info(phase, info):
    print(f"{phase}: ConvergenceInfo outer={int(info.outer_iters)} "
          f"inner={int(info.inner_iters)} "
          f"marginal_err={float(info.marginal_err):.3e} "
          f"converged={bool(info.converged)}", flush=True)


def _peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


class Checks:
    """Every checked error, printed beside its bound; a miss fails the run."""

    def __init__(self):
        self.failed: list[str] = []

    def __call__(self, phase, name, err, bound, reason):
        ok = bool(np.isfinite(err)) and err <= bound
        print(f"{phase}: check {name} err={err:.3e} bound={bound:.3e} "
              f"{'ok' if ok else 'FAIL'} ({reason})", flush=True)
        if not ok:
            self.failed.append(f"{phase}/{name}")


def _rel(a, b):
    """max |a − b| / max |b|."""
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def _measure(key, n):
    u = jax.random.uniform(key, (n,), jnp.float32) + 1e-3
    return u / u.sum()


def _dense_problem(key, side):
    from repro.core.grids import Grid2D
    from repro.core.gw import GWConfig

    grid = Grid2D(side, 1.0 / (side - 1), 1)
    kx, ky = jax.random.split(key)
    cfg = GWConfig(eps=4e-3, eps_init=5e-2, anneal_decay=0.5, outer_iters=8,
                   sinkhorn_iters=200, sinkhorn_chunk=25, tol=1e-4,
                   backend="cumsum", sinkhorn_backend="auto")
    return grid, _measure(kx, grid.size), _measure(ky, grid.size), cfg


def dense_segment_has_kernel(key, side=DENSE_SIDE) -> bool:
    """Does one outer step of the dense solve lower to a Pallas TPU kernel
    (``tpu_custom_call``) — the step the one-shot, batched and served
    solves all share?"""
    from repro.core.coupling import full_init
    from repro.core.gradient import GradientOperator
    from repro.core.gw import gw_plan_segment
    from repro.core.solver import SolveControls, init_carry

    grid, mu, nu, cfg = _dense_problem(key, side)

    def segment(mu, nu, carry):
        op = GradientOperator(grid, grid, cfg.backend)
        c1, _, _ = op.constant_term(mu, nu)
        return gw_plan_segment(op, c1, mu, nu, cfg,
                               SolveControls.from_config(cfg), carry, 1)

    carry = init_carry(full_init(mu, nu), cfg.outer_iters)
    return "tpu_custom_call" in jax.jit(segment).lower(mu, nu,
                                                       carry).as_text()


def phase_dense(check, key, side=DENSE_SIDE):
    from repro.core.gradient import GradientOperator
    from repro.core.grids import gw_product, gw_product_dense
    from repro.core.gw import entropic_gw
    from repro.kernels import ops

    grid, mu, nu, cfg = _dense_problem(key, side)
    n = grid.size

    def solve(c):
        return lambda mu, nu: entropic_gw(grid, grid, mu, nu, c)

    res, t_c, t_r = _timed(solve(cfg), mu, nu)
    print(f"dense: N=M={n} compile_s={t_c:.2f} run_s={t_r:.2f} "
          f"value={float(res.value):.6e}", flush=True)
    _info("dense", res.info)

    plan = res.plan
    fgc, t_c, t_r = _timed(lambda p: gw_product(grid, grid, p, "cumsum"),
                           plan)
    print(f"dense: fgc D_X Γ D_Y compile_s={t_c:.2f} run_s={t_r:.3f}",
          flush=True)

    def dense(p):
        return gw_product_dense(grid, grid, p)

    with jax.default_matmul_precision("highest"):
        ref = jax.jit(dense)(plan)
    # both sides are f32 arithmetic (the reference at "highest" matmul
    # precision); on a CPU the cumsum apply erred 2.4e-7 at this N.  A
    # product taken at bf16 input precision errs ~2^-9 per entry: the info
    # line prints what the TPU's default precision gives here
    check("dense", "fgc_product_vs_dense_highest", _rel(fgc, ref), 1e-5,
          "f32 cumsum moments vs f32 matmul")
    print(f"dense: info dense_product_at_default_precision_err="
          f"{_rel(jax.jit(dense)(plan), ref):.3e}", flush=True)
    del fgc, ref

    op = GradientOperator(grid, grid, cfg.backend)
    cost = jax.jit(lambda p, mu, nu: op.grad(
        p, op.constant_term(mu, nu)[0]))(plan, mu, nu)
    del plan
    eps = jnp.asarray(cfg.eps, jnp.float32)
    log_mu, log_nu = jnp.log(mu), jnp.log(nu)
    f_k = jax.jit(ops.sinkhorn_row_update)(cost, res.g, log_mu, eps)
    lse = jax.jit(lambda c, g: jax.nn.logsumexp((g[None, :] - c) / eps,
                                                axis=1))(cost, res.g)
    g_k = jax.jit(ops.sinkhorn_col_update)(cost, f_k, log_nu, eps)
    lse_c = jax.jit(lambda c, f: jax.nn.logsumexp((f[:, None] - c) / eps,
                                                  axis=0))(cost, f_k)
    del cost
    # f = ε(log μ − lse): the kernel's online LSE reassociates the sum over
    # 128-wide tiles (≤ 1 ulp of lse per half-step on the CPU); 64 ulps of
    # the largest |lse| also admit Mosaic's exp/log differing from XLA's
    for name, got, lse_x, logm in (("row", f_k, lse, log_mu),
                                   ("col", g_k, lse_c, log_nu)):
        want = eps * (logm - lse_x)
        bound = float(eps * 64 * np.spacing(np.float32(
            jnp.max(jnp.abs(lse_x)))))
        check("dense", f"pallas_{name}_half_step_vs_xla_logsumexp",
              float(jnp.max(jnp.abs(got - want))), bound,
              "64 ulps of max|lse|, times ε")

    res_x, t_c, t_r = _timed(
        solve(dataclasses.replace(cfg, sinkhorn_backend="xla")), mu, nu)
    print(f"dense: xla-backend solve compile_s={t_c:.2f} run_s={t_r:.2f} "
          f"value={float(res_x.value):.6e}", flush=True)
    _info("dense/xla", res_x.info)
    check("dense", "value_vs_xla_backend",
          abs(float(res.value) - float(res_x.value)) / abs(float(res_x.value)),
          1e-3, "both stop their inner solves at tol=1e-4, so the plans "
          "may differ at that level")


def _cloud(key, n):
    from repro.core.geometry import PointCloudGeometry

    kp, km = jax.random.split(key)
    return (PointCloudGeometry(jax.random.uniform(kp, (n, 3), jnp.float32)),
            _measure(km, n))


def phase_served(check, key, sizes=SERVED_SIZES, repeats=SERVED_REPEATS):
    from collections import Counter

    from repro.core.gw import GWConfig, entropic_gw
    from repro.serve.engine import GWEngine, GWServeConfig

    uniques = []
    for i, n in enumerate(sizes):
        (gx, mu), (gy, nu) = (_cloud(k, n) for k in
                              jax.random.split(jax.random.fold_in(key, i)))
        uniques.append((gx, gy, mu, nu))
    # ε = 0.2 on unit-cube clouds converges in ~10 outer steps; at 0.05 and
    # 0.1 the plan still moves after 60, so two solves of the same problem
    # would be compared mid-flight
    solver = GWConfig(eps=0.2, eps_init=1.0, anneal_decay=0.5,
                      outer_iters=30, sinkhorn_iters=200, sinkhorn_chunk=25)

    def engine(cache):
        return GWEngine(GWServeConfig(
            solver=solver, scheduler="pipeline", tol=1e-4, max_batch=8,
            cache_capacity=cache))

    t0 = time.perf_counter()
    warm = engine(0)
    list(warm.serve(uniques))           # compiles every bucket × width
    print(f"served: first pass, with compilation, cache off "
          f"run_s={time.perf_counter() - t0:.2f}", flush=True)
    eng = engine(64)
    rids, results = [], {}
    for wave, stream in (("unique", uniques),
                         ("repeat", [uniques[i] for i in repeats])):
        first = len(results)            # a fresh engine numbers rids 0, 1, …
        t0 = time.perf_counter()
        for rid, res in eng.serve(stream):
            rids.append(rid)
            results[rid] = res
        jax.block_until_ready([r.value for r in results.values()])
        t = time.perf_counter() - t0
        s = eng.stats
        print(f"served: wave={wave} requests={len(stream)} run_s={t:.2f} "
              f"dispatches={s['dispatches']} cache hits/warm/miss="
              f"{s['cache_hits']}/{s['cache_warm_starts']}/"
              f"{s['cache_misses']} errors={len(eng.last_errors)}",
              flush=True)
        if eng.last_errors:
            raise RuntimeError(f"served {wave}: bucket failures "
                               f"{eng.last_errors}")
        if wave == "repeat" and s["cache_hits"] != len(stream):
            raise RuntimeError(f"served: {s['cache_hits']} of {len(stream)} "
                               "repeats were cache hits")
        counts = Counter(r for r in rids if r >= first)
        if (set(counts) != set(range(first, first + len(stream)))
                or max(counts.values()) != 1):
            raise RuntimeError(f"served {wave}: rids {sorted(counts)} are "
                               "not each returned exactly once")
    for i in (0, 2):                    # one N = 1024 and one N = 4096
        gx, gy, mu, nu = uniques[i]
        cfg = eng.cfg.solver_cfg()
        one = jax.jit(lambda *p: entropic_gw(*p, cfg))(gx, gy, mu, nu)
        got = results[i]
        _info(f"served/request{i}/N={gx.size}", got.info)
        _info(f"served/request{i}/one-shot", one.info)
        check("served", f"request{i}_value_vs_one_shot",
              abs(float(got.value) - float(one.value)) / abs(float(one.value)),
              1e-3, "the same iterates in a vmapped lane, up to rounding")


def phase_factored(check, key, n=LR_N, rank=LR_RANK):
    from repro.core.gw import GWConfig, entropic_gw

    (gx, mu), (gy, nu) = (_cloud(k, n) for k in jax.random.split(key))
    gx, gy = gx.to_low_rank(), gy.to_low_rank()
    # tol = 0: both backends run exactly outer_iters × sinkhorn_iters
    cfg = GWConfig(eps=5e-2, outer_iters=10, sinkhorn_iters=50,
                   sinkhorn_chunk=25, tol=0.0, plan="lowrank",
                   plan_rank=rank, lowrank_backend="auto")
    out = {}
    for backend in ("auto", "xla"):
        c = dataclasses.replace(cfg, lowrank_backend=backend)
        res, t_c, t_r = _timed(lambda *p: entropic_gw(*p, c), gx, gy, mu,
                               nu)
        print(f"factored: N={n} rank={rank} lowrank_backend={backend} "
              f"compile_s={t_c:.2f} run_s={t_r:.2f} "
              f"value={float(res.value):.6e}", flush=True)
        _info(f"factored/{backend}", res.info)
        out[backend] = res
    p, x = out["auto"], out["xla"]
    check("factored", "marginal_err_vs_xla",
          abs(float(p.marginal_err) - float(x.marginal_err)), 1e-3,
          "L1 gap of unit-mass marginals; a kernel fault moves it by O(0.1)")
    check("factored", "value_vs_xla",
          abs(float(p.value) - float(x.value)) / abs(float(x.value)), 1e-2,
          "the XLA side's factor matmuls run at the TPU's default precision "
          "(one bf16 pass, ~2^-9 per product)")


def phase_gradient(check, key, n=GRAD_N):
    from repro.core.gw import GWConfig, entropic_gw

    (gx, mu), (gy, nu) = (_cloud(k, n) for k in jax.random.split(key))
    # the implicit VJP linearizes at a fixed point, so the forward solves
    # must converge: the served phase's ε, and tol = 1e-4 because on a v5e
    # the f32 marginal error of these plans levels off near 3e-5
    cfg = GWConfig(eps=0.2, eps_init=1.0, anneal_decay=0.5, outer_iters=30,
                   sinkhorn_iters=200, sinkhorn_chunk=25, tol=1e-4)
    grads = {}
    for backend in ("auto", "xla"):
        c = dataclasses.replace(cfg, sinkhorn_backend=backend)
        def value(mu, gx, gy, nu, c=c):
            res = entropic_gw(gx, gy, mu, nu, c)
            return res.value, res.info

        (g, info), t_c, t_r = _timed(jax.grad(value, has_aux=True),
                                     mu, gx, gy, nu)
        print(f"gradient: N=M={n} sinkhorn_backend={backend} "
              f"compile_s={t_c:.2f} run_s={t_r:.2f} "
              f"|grad|_max={float(jnp.max(jnp.abs(g))):.6e}", flush=True)
        _info(f"gradient/{backend}", info)
        if not bool(jnp.all(jnp.isfinite(g))):
            raise RuntimeError(f"gradient ({backend}) is not finite")
        grads[backend] = g - jnp.mean(g)    # μ lives on the simplex
    check("gradient", "grad_mu_vs_xla_backend",
          _rel(grads["auto"], grads["xla"]), 1e-2,
          "forward solves agree to tol=1e-4; the implicit correction can "
          "amplify that by 1/(1-ρ)")


PHASES = {"dense": phase_dense, "served": phase_served,
          "factored": phase_factored, "gradient": phase_gradient}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every generated measure and point cloud")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              "nothing was run", file=sys.stderr)
        return 1
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.kernels import ops
    from repro.launch.compile_cache import use_compile_cache

    jax.config.update("jax_enable_x64", False)
    print(f"chip_smoke: compile cache at {use_compile_cache()}", flush=True)
    warnings.simplefilter("error")
    for knob, resolve in (("sinkhorn", ops.resolve_sinkhorn_backend),
                          ("lowrank", ops.resolve_lowrank_backend)):
        if resolve("auto") != "pallas":
            print(f"chip_smoke: {knob} backend 'auto' resolves to "
                  f"{resolve('auto')!r}, not 'pallas'", file=sys.stderr)
            return 1
    key = jax.random.PRNGKey(args.seed)
    if not dense_segment_has_kernel(key):
        print("chip_smoke: the dense segment step lowers without a Pallas "
              "kernel (no tpu_custom_call)", file=sys.stderr)
        return 1

    check = Checks()
    errors = []
    for i, (name, phase) in enumerate(PHASES.items()):
        t0 = time.perf_counter()
        try:
            phase(check, jax.random.fold_in(key, i))
        except Exception:   # noqa: BLE001 — report, then run the next phase
            traceback.print_exc()
            errors.append(name)
        print(f"{name}: phase_s={time.perf_counter() - t0:.2f} "
              f"peak_bytes_in_use={_peak_bytes()}", flush=True)
    if errors or check.failed:
        print(f"chip_smoke: failed phases {errors}, failed checks "
              f"{check.failed}", file=sys.stderr)
        return 1
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
