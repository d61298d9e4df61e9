"""Implicit vs unrolled gradients through the GW solver: wall-clock and
peak memory of value-and-grad across problem sizes.

Run:  PYTHONPATH=src python benchmarks/grad_bench.py [--out BENCH_grad.json]
      (--smoke: tiny sizes so CI merely executes every mode)

Setup: the trainer's FGW sequence-alignment loss (hidden states (N, d)
against a fixed teacher, positions as structure) differentiated with
respect to the student hidden states — the exact shape train/loop.py
back-propagates.  Two gradient constructions over the same solve:

  unrolled   plain reverse-mode AD through a python-unrolled mirror
             descent (the pre-refactor ``unroll=True`` semantics, kept
             here as a reference implementation only): every inner
             logsumexp of every outer step is stored for the backward
             pass, so peak memory grows with outer_iters × sinkhorn
             pairs.
  implicit   the solver stack's `fixed_point_value` surface: the forward
             solve runs the convergence-controlled driver (any backend),
             the backward pass is rebuilt from the converged coupling
             alone — O(1) solve memory, iteration counts invisible to AD.

Both constructions are run at a CONVERGED solve (where the implicit
gradient's contract holds) and the gradients are compared; the acceptance
flags require agreement plus the memory win at the largest size.

A third mode benches the train-side batch loss
(`losses.fgw_alignment_loss_batch` — ragged lanes, one vmapped solve)
end-to-end under value_and_grad, which is the per-step distillation cost
a training run pays.

Each case runs in a SUBPROCESS (``--case mode:n``) so peak memory is a
real per-case ``ru_maxrss``.  Emits BENCH_grad.json.
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

FULL_SIZES = [64, 128, 256]
SMOKE_SIZES = [24, 48]
# Regime chosen so BOTH constructions converge: ε large enough that the
# outer mirror map contracts well inside OUTERS steps, and the implicit
# backward's Neumann series run long enough that its tail ρ^k/(1−ρ) is
# negligible (ρ ≈ 0.96 here → 1200 terms ≈ 3e-10 tail).  The early exit
# makes the long cap free on faster-contracting problems.
OUTERS, PAIRS = 40, 100
NEUMANN = 1200
THETA, EPS, DIM = 0.5, 1.5e-1, 16


def _problem(n: int):
    import jax.numpy as jnp
    import numpy as np

    r = np.random.default_rng(0)
    h_src = jnp.asarray(r.normal(size=(n, DIM)))
    h_tgt = jnp.asarray(r.normal(size=(n + 8, DIM)))
    return h_src, h_tgt


def _run_case(mode: str, n: int) -> dict:
    import jax

    from repro.launch.compile_cache import use_compile_cache

    jax.config.update("jax_enable_x64", True)
    use_compile_cache()
    import jax.numpy as jnp
    import numpy as np

    from repro.core import losses as gw_losses

    if mode == "distill":
        # the trainer's path: ragged batch, one vmapped solve
        h0, t0_ = _problem(n)
        h1, t1 = _problem(max(n - n // 4, 4))
        cfg = gw_losses.AlignConfig(theta=THETA, eps=EPS,
                                    outer_iters=OUTERS,
                                    sinkhorn_iters=PAIRS,
                                    implicit_solve_iters=NEUMANN)

        def loss(a0, a1):
            return gw_losses.fgw_alignment_loss_batch([a0, a1], [t0_, t1],
                                                      cfg)

        fn = jax.value_and_grad(loss, argnums=(0, 1))
        (v, g), wall = _timed(jax, fn, h0, h1)
        return {"mode": mode, "n": n, "wall_s": wall,
                "peak_rss_mb": _rss_mb(), "value": float(v),
                "grad_finite": bool(jnp.isfinite(g[0]).all()
                                    and jnp.isfinite(g[1]).all())}

    h_src, h_tgt = _problem(n)
    if mode == "implicit":
        cfg = gw_losses.AlignConfig(theta=THETA, eps=EPS,
                                    outer_iters=OUTERS,
                                    sinkhorn_iters=PAIRS,
                                    implicit_solve_iters=NEUMANN)

        def loss(h):
            return gw_losses.fgw_alignment_loss(h, h_tgt, cfg)
    elif mode == "unrolled":
        from repro.core import sinkhorn as sk
        from repro.core.fgw import fgw_full_value
        from repro.core.geometry import as_geometry
        from repro.core.gradient import GradientOperator
        from repro.core.grids import Grid1D
        from repro.core.losses import _feature_cost

        s, t = h_src.shape[0], h_tgt.shape[0]
        gx = as_geometry(Grid1D(s, 1.0 / (s - 1), 1), "cumsum")
        gy = as_geometry(Grid1D(t, 1.0 / (t - 1), 1), "cumsum")
        mu = jnp.full((s,), 1.0 / s)
        nu = jnp.full((t,), 1.0 / t)
        op = GradientOperator(gx, gy, "cumsum")
        c1, _, _ = op.constant_term(mu, nu)

        def loss(h):
            feat = _feature_cost(h, h_tgt)
            c2 = (1.0 - THETA) * feat ** 2 + THETA * c1
            plan = mu[:, None] * nu[None, :]
            f, g = jnp.zeros_like(mu), jnp.zeros_like(nu)
            for _ in range(OUTERS):
                cost = c2 - 4.0 * THETA * op.product(plan)
                f, g = sk.sinkhorn_step_diff(cost, mu, nu, EPS, f, g,
                                             pairs=PAIRS)
                plan = jnp.exp((f[:, None] + g[None, :] - cost) / EPS)
            return fgw_full_value(op, feat, plan, THETA)
    else:
        raise ValueError(f"unknown mode {mode!r}")

    fn = jax.value_and_grad(loss)
    (v, g), wall = _timed(jax, fn, h_src)
    return {"mode": mode, "n": n, "wall_s": wall,
            "peak_rss_mb": _rss_mb(), "value": float(v),
            "grad_norm": float(jnp.linalg.norm(g)),
            "grad_head": np.asarray(g).ravel()[:8].tolist()}


def _timed(jax, fn, *args):
    out = fn(*args)                       # compile + first run
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    out = fn(*args)
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def _rss_mb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def _spawn_case(mode: str, n: int) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(_REPO / "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    out = subprocess.run(
        [sys.executable, __file__, "--case", f"{mode}:{n}"],
        capture_output=True, text=True, check=True, cwd=_REPO, env=env)
    return json.loads(out.stdout.strip().splitlines()[-1])


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_grad.json")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--case", default=None, help="internal: run one case "
                    "in-process and print its JSON (mode:n)")
    args = ap.parse_args()

    if args.case:
        mode, n = args.case.split(":")
        print(json.dumps(_run_case(mode, int(n))))
        return

    sizes = SMOKE_SIZES if args.smoke else FULL_SIZES
    cases: list[dict] = []
    for n in sizes:
        for mode in ("unrolled", "implicit"):
            print(f"[grad_bench] {mode:9s} n={n} ...", flush=True)
            cases.append(_spawn_case(mode, n))
            print(f"    {cases[-1]['wall_s']:.3f}s "
                  f"{cases[-1]['peak_rss_mb']:.0f} MB", flush=True)
    n_d = sizes[-1]
    print(f"[grad_bench] distill   n={n_d} ...", flush=True)
    cases.append(_spawn_case("distill", n_d))
    print(f"    {cases[-1]['wall_s']:.3f}s "
          f"{cases[-1]['peak_rss_mb']:.0f} MB", flush=True)

    def _pick(mode, n):
        return next(c for c in cases
                    if c["mode"] == mode and c["n"] == n)

    nmax = sizes[-1]
    u, i = _pick("unrolled", nmax), _pick("implicit", nmax)
    rel = abs(u["grad_norm"] - i["grad_norm"]) / max(u["grad_norm"], 1e-12)
    head = float(max(abs(a - b) for a, b in
                     zip(u["grad_head"], i["grad_head"])))
    acceptance = {
        "n": nmax,
        # converged solves: the two constructions compute the same gradient
        "grads_match": bool(rel < 1e-6 and head < 1e-8),
        # the implicit backward pays no per-iteration storage
        "implicit_mem_no_worse": bool(
            i["peak_rss_mb"] <= u["peak_rss_mb"] * 1.05),
        "distill_value_and_grad_finite": bool(
            _pick("distill", n_d)["grad_finite"]),
    }
    report = {"mode": "smoke" if args.smoke else "full",
              "iters": {"outer": OUTERS, "pairs": PAIRS,
                        "neumann": NEUMANN},
              "cases": cases, "acceptance": acceptance}
    Path(args.out).write_text(json.dumps(report, indent=2) + "\n")
    print(json.dumps(acceptance, indent=2))


if __name__ == "__main__":
    main()
