"""Sliced fast tier: estimator latency/accuracy, the cache's profile
second stage on rotated/re-indexed repeat traffic, and the calibrated
hardness predictor.

Run:  PYTHONPATH=src python benchmarks/sliced_bench.py [--out BENCH_sliced.json]
      (--smoke: tiny sizes so CI merely executes every code path)

Three cases, one JSON:

  latency   `sliced_gw` vs the full entropic solve over a size sweep —
            wall-clock per answer (both jit-warmed) and the estimate's
            relative gap to the converged entropic value.  The sliced
            answer is a lower-fidelity product (monotone 1D transports
            averaged over directions), so the gap is REPORTED, not gated;
            the latency ratio is the point of the tier.  Also records the
            single-dispatch / jit-stability contract of the
            ``service="sliced"`` class: over a stream of ragged sizes in
            one bucket the engine must issue exactly one dispatch per
            request and compile at most one new sliced executable.
  cache     the acceptance stream for the profile second stage: fresh
            point-cloud traffic mixed with ~30% rotated + re-indexed
            repeats.  Every repeat misses every byte digest; the gate is
            the majority of them converting into profile warm starts that
            converge in strictly fewer outer iterations to the same
            optimum (value within rtol 1e-3 of the cold solve).
  hardness  rank correlation (Spearman) of predicted vs observed outer
            iterations on a held-out stream, for the hand-tuned formula
            and for the online ridge calibrator trained by serving one
            warmup stream.  Gate: the calibrated predictor is at least
            non-inferior (corr ≥ formula − 0.05).

Emits BENCH_sliced.json with per-case metrics and acceptance flags.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from repro.core import GWConfig, entropic_gw
from repro.core.geometry import PointCloudGeometry
from repro.core.sliced import _sliced_core, sliced_gw
from repro.launch.compile_cache import use_compile_cache
from repro.serve.engine import GWEngine, GWServeConfig

_REPO = Path(__file__).resolve().parent.parent

SOLVER = GWConfig(eps=2e-1, outer_iters=80, sinkhorn_iters=300,
                  sinkhorn_chunk=25, backend="dense", eps_init=1.0,
                  anneal_decay=0.7)
TOL = 1e-4


def _cloud_problem(m, n, seed, d=2):
    r = np.random.default_rng(seed)
    gx = PointCloudGeometry(jnp.asarray(r.normal(size=(m, d))))
    gy = PointCloudGeometry(jnp.asarray(r.normal(size=(n, d))))
    mu = r.random(m) + 0.5
    nu = r.random(n) + 0.5
    return (gx, gy, jnp.asarray(mu / mu.sum()), jnp.asarray(nu / nu.sum()))


def _rot_perm(prob, seed):
    """Semantically the same problem: each side independently rotated
    (isometry) and re-indexed (atoms + weights permuted together)."""
    r = np.random.default_rng(seed)

    def side(g, w):
        p, wn = np.asarray(g.points), np.asarray(w)
        th = r.uniform(0.0, 2.0 * np.pi)
        q = np.array([[np.cos(th), -np.sin(th)], [np.sin(th), np.cos(th)]])
        perm = r.permutation(len(p))
        return (PointCloudGeometry(jnp.asarray((p @ q.T)[perm]), g.metric),
                jnp.asarray(wn[perm]))

    gx, gy, mu, nu = prob
    (gx2, mu2), (gy2, nu2) = side(gx, mu), side(gy, nu)
    return (gx2, gy2, mu2, nu2)


def _timed(fn, reps):
    fn()                                    # warm (compile + autotune)
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps


# ---------------------------------------------------------------------------
# case: latency + accuracy sweep, and the single-dispatch contract
# ---------------------------------------------------------------------------

def case_latency(smoke: bool) -> dict:
    sizes = [12, 16] if smoke else [16, 32, 64, 128]
    reps = 3 if smoke else 10
    cfg = GWConfig(eps=2e-1,
                   outer_iters=40 if smoke else 80,
                   sinkhorn_iters=200 if smoke else 300,
                   backend="dense", eps_init=1.0, anneal_decay=0.7,
                   tol=TOL)
    rows = []
    for n in sizes:
        gx, gy, mu, nu = _cloud_problem(n, n, 1000 + n)
        exact = entropic_gw(gx, gy, mu, nu, cfg)

        def run_exact():
            jax.block_until_ready(entropic_gw(gx, gy, mu, nu, cfg).plan)

        def run_sliced():
            jax.block_until_ready(
                sliced_gw(gx, gy, mu, nu, n_proj=32).profile)

        t_exact = _timed(run_exact, reps)
        t_sliced = _timed(run_sliced, reps)
        est = float(sliced_gw(gx, gy, mu, nu, n_proj=32).estimate)
        v = float(exact.value)
        rows.append({
            "n": n, "exact_seconds": t_exact, "sliced_seconds": t_sliced,
            "speedup": t_exact / max(t_sliced, 1e-12),
            "exact_value": v, "sliced_estimate": est,
            "relative_gap": abs(est - v) / max(abs(v), 1e-12),
        })
        print(f"    n={n:4d}  exact {t_exact * 1e3:8.2f} ms   sliced "
              f"{t_sliced * 1e3:7.2f} ms  ({rows[-1]['speedup']:6.1f}×)  "
              f"gap {rows[-1]['relative_gap']:.2f}", flush=True)

    # the service contract: one dispatch per request, one executable per
    # bucket even across ragged true sizes
    eng = GWEngine(GWServeConfig(
        solver=SOLVER, max_batch=4, size_bucket=16, tol=TOL,
        scheduler="pipeline", segment_iters=5, service="sliced"))
    stream = [_cloud_problem(m, n, 2000 + i)
              for i, (m, n) in enumerate([(9, 11), (12, 8), (10, 14),
                                          (11, 11)])]
    jit0 = _sliced_core._cache_size()
    for p in stream:
        eng.submit(*p)
    out = eng.flush()
    new_exec = _sliced_core._cache_size() - jit0
    contract = {
        "n_requests": len(stream),
        "dispatches": eng.stats["dispatches"],
        "sliced_answers": eng.stats["sliced_answers"],
        "new_executables": new_exec,
        "single_dispatch": bool(eng.stats["dispatches"] == len(stream)),
        "jit_cache_stable": bool(new_exec <= 1),
    }
    print(f"    service=sliced: {contract['dispatches']} dispatches / "
          f"{len(stream)} requests, {new_exec} new executable(s)",
          flush=True)
    assert len(out) == len(stream)
    return {
        "case": "latency", "sizes": sizes, "n_proj": 32, "rows": rows,
        "service_contract": contract,
        "accept_service": bool(contract["single_dispatch"]
                               and contract["jit_cache_stable"]),
    }


# ---------------------------------------------------------------------------
# case: profile second stage on the rotated-repeat stream
# ---------------------------------------------------------------------------

def case_cache(smoke: bool) -> dict:
    n_base = 4 if smoke else 8
    n_mixed = 10 if smoke else 30
    eng = GWEngine(GWServeConfig(
        solver=SOLVER, max_batch=4, size_bucket=16, tol=TOL,
        scheduler="pipeline", segment_iters=5, cache_capacity=64,
        cache_near_tol=1e-3, cache_profile_tol=0.08))
    bases = [_cloud_problem(10, 12, 3000 + i) for i in range(n_base)]
    cold_rids = [eng.submit(*p) for p in bases]
    res = eng.flush()
    cold = [res[r] for r in cold_rids]

    rng = np.random.default_rng(7)
    repeats, fresh = [], []
    for j in range(n_mixed):
        if j % 3 == 0:                       # ~30% repeat traffic
            i = int(rng.integers(n_base))
            repeats.append((i, eng.submit(*_rot_perm(bases[i], 4000 + j))))
        else:
            fresh.append(eng.submit(*_cloud_problem(10, 12, 5000 + j)))
    out = eng.flush()

    converted = eng.stats["cache_profile_hits"]
    savings, same_opt = [], 0
    for i, rid in repeats:
        w, c = out[rid], cold[i]
        savings.append(int(c.info.outer_iters) - int(w.info.outer_iters))
        if (abs(float(w.value) - float(c.value))
                <= 1e-3 * abs(float(c.value)) + 1e-6):
            same_opt += 1
    mean_cold = float(np.mean([int(c.info.outer_iters) for c in cold]))
    result = {
        "case": "cache", "n_base": n_base, "n_mixed": n_mixed,
        "n_repeats": len(repeats), "repeat_frac": len(repeats) / n_mixed,
        "exact_hits": eng.stats["cache_hits"],
        "profile_hits": converted,
        "mean_cold_outer_iters": mean_cold,
        "warm_outer_savings": savings,
        "repeats_at_same_optimum": same_opt,
        "accept_majority_converted": bool(2 * converted > len(repeats)),
        "accept_strictly_fewer_iters": bool(
            all(s > 0 for s in savings) and same_opt == len(repeats)),
    }
    print(f"    {converted}/{len(repeats)} repeats converted to warm "
          f"starts; outer savings {savings} (cold mean {mean_cold:.1f}); "
          f"{same_opt}/{len(repeats)} at the cold optimum", flush=True)
    return result


# ---------------------------------------------------------------------------
# case: calibrated vs hand-tuned hardness ranking
# ---------------------------------------------------------------------------

def _spearman(a, b):
    ra = np.argsort(np.argsort(a)).astype(np.float64)
    rb = np.argsort(np.argsort(b)).astype(np.float64)
    if ra.std() == 0 or rb.std() == 0:
        return 0.0
    return float(np.corrcoef(ra, rb)[0, 1])


def case_hardness(smoke: bool) -> dict:
    eps_menu = [3e-1, 2e-1, 1e-1, 5e-2]
    n_train = 24 if smoke else 48
    n_test = 10 if smoke else 16

    def stream(n, seed0):
        rng = np.random.default_rng(seed0)
        out = []
        for i in range(n):
            base = _cloud_problem(10, 12, seed0 + i)
            # half the traffic is an isometric pair (easy: the solver
            # converges fast) — hardness the sliced estimate sees and the
            # eps-only formula cannot.  The copy's weights are permuted
            # WITH its atoms, so the pair really is the same space twice.
            if rng.random() < 0.5:
                gx, _, mu, _ = base
                copy = _rot_perm((gx, gx, mu, mu), seed0 + 91 * i)
                out.append(((gx, copy[1], mu, copy[3]),
                            eps_menu[i % len(eps_menu)]))
            else:
                out.append((base, eps_menu[i % len(eps_menu)]))
        return out

    # cache_profile_tol > 0 makes every admitted request compute its
    # sliced estimate (the cache's second stage needs the profile), which
    # is the calibrator's differentiating feature — every problem here is
    # distinct, so no request actually profile-matches and none warm-start
    common = dict(solver=SOLVER, max_batch=4, size_bucket=16, tol=TOL,
                  scheduler="pipeline", segment_iters=5, cache_capacity=64,
                  cache_near_tol=1e-3, cache_profile_tol=0.08)
    trained = GWEngine(GWServeConfig(calibrate_hardness=True,
                                     calib_min_obs=8, **common))
    for prob, eps in stream(n_train, 6000):
        trained.submit(*prob, eps=eps)
    trained.flush()
    n_obs = trained.calib.observations

    formula = GWEngine(GWServeConfig(calibrate_hardness=False, **common))
    test = stream(n_test, 7000)
    pred_cal, pred_form, observed = [], [], []
    for prob, eps in test:
        for eng, preds in ((trained, pred_cal), (formula, pred_form)):
            rid = eng.submit(*prob, eps=eps)
            req = eng._queue[-1]
            eng._resolve(req)
            # the admission sequence: cache consult (which computes the
            # sliced profile/estimate feature) precedes hardness ordering
            eng._cache_lookup(req, {}, set())
            preds.append(float(eng.predicted_hardness(req)))
    out_t = trained.flush()
    formula.flush()
    observed = [int(out_t[r].info.outer_iters) for r in sorted(out_t)]

    corr_cal = _spearman(pred_cal, observed)
    corr_form = _spearman(pred_form, observed)
    # smoke trains on too few observations for a fair ranking comparison
    # (ridge barely past min_obs) — its gate only checks the calibrated
    # path learned SOMETHING; the real margin binds on the full run
    margin = 0.35 if smoke else 0.05
    result = {
        "case": "hardness", "n_train": n_train, "n_test": n_test,
        "train_observations": n_obs, "eps_menu": eps_menu,
        "spearman_calibrated": corr_cal,
        "spearman_formula": corr_form,
        "noninferiority_margin": margin,
        "accept_noninferior": bool(corr_cal >= corr_form - margin),
    }
    print(f"    rank correlation with observed outer iters: calibrated "
          f"{corr_cal:+.2f} vs formula {corr_form:+.2f} "
          f"({n_obs} training observations)", flush=True)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes: execute every path in CI")
    args = ap.parse_args()
    use_compile_cache()

    cases = {}
    for name, fn in (("latency", case_latency), ("cache", case_cache),
                     ("hardness", case_hardness)):
        print(f"[sliced_bench] {name} ...", flush=True)
        cases[name] = fn(args.smoke)

    out = {
        "backend": jax.default_backend(), "smoke": bool(args.smoke),
        "cases": cases,
        "summary": {
            "sliced_speedup_at_max_n": cases["latency"]["rows"][-1][
                "speedup"],
            "repeats_converted_frac": (
                cases["cache"]["profile_hits"]
                / max(cases["cache"]["n_repeats"], 1)),
            "spearman_calibrated": cases["hardness"]["spearman_calibrated"],
            "acceptance": bool(
                cases["latency"]["accept_service"]
                and cases["cache"]["accept_majority_converted"]
                and cases["cache"]["accept_strictly_fewer_iters"]
                and cases["hardness"]["accept_noninferior"]),
        },
    }
    dest = args.out or str(_REPO / "BENCH_sliced.json")
    Path(dest).write_text(json.dumps(out, indent=2) + "\n")
    print(f"wrote {dest}")
    return 0 if out["summary"]["acceptance"] or args.smoke else 1


if __name__ == "__main__":
    sys.exit(main())
