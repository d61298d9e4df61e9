"""Continuous-batching vs flush-barrier GW serving on a mixed-difficulty
stream — does harvest-and-refill actually reclaim the straggler waste?

Run:  PYTHONPATH=src python benchmarks/serve_bench.py [--out BENCH_serve.json]
      (--smoke: tiny sizes so CI merely executes the serving path)

Setup: one `GWEngine` bucket (equal-sized 1D grids) receives a stream of
requests whose per-request ε spans easy→hard (annealed); difficulty — and
therefore outer-iteration count — varies several-fold across the stream.
The same stream is flushed through both schedulers:

  barrier     the PR-3 path: power-of-two chunks through
              `entropic_gw_batch`; every chunk burns flops until its
              SLOWEST lane converges, so each easy lane pays for the
              hardest lane it was chunked with.
  continuous  the slot scheduler: bounded segments (``segment_iters`` outer
              steps per dispatch), converged lanes harvested and their
              slots refilled between segments — an easy lane's slot is
              reused by the next request instead of idling masked.

Metrics (from ``engine.stats``): wall-clock of the flush, and executed vs
useful lane-iterations — "executed" counts what the vmap lockstep
physically burns (batch width × the slowest lane's advance per dispatch),
"useful" what requests actually needed.  For the barrier mode the executed
count is estimated as width × max(total per-lane iterations) per chunk,
which UNDERcounts its true lockstep cost (max of sums ≤ sum of per-window
maxes) — the comparison is biased against the continuous scheduler, so a
win here is a real win.  Exactness is asserted, not assumed: both
schedulers must return identical iteration counts and near-identical plans
for every request.

Emits BENCH_serve.json with per-mode metrics and the acceptance flags
(continuous beats barrier on wall-clock AND executed inner iterations).

--pipeline mode (PR-8) benches the async multi-bucket dispatcher and the
plan cache instead, three cases each in its OWN SUBPROCESS (fresh jit
caches, per-case ru_maxrss):

  stream   mixed-difficulty requests over several size buckets, flushed
           through scheduler="continuous" (buckets strictly one after
           another) vs "pipeline" (up to max_inflight_buckets segment
           dispatches in flight, ready-first harvest).  Result-identical
           is ASSERTED (same slot widths, identical iteration counts,
           plans to donated-executable roundoff).  Acceptance: the
           pipeline must reclaim ≥50% of the serial scheduler's
           device-idle time, and deliver wall-clock ≥1.2× wherever the
           host can physically overlap (>1 CPU core — on a single-core
           host the reclaimed idle cannot become wall-clock, so only
           no-regression is gated and the measured speedup is recorded
           as-is).
  repeat   a 50%-repeat-traffic phase against a warmed plan cache vs the
           same stream served cold (cache_capacity=0).  Exact hits must
           answer with ZERO segment dispatches; acceptance is throughput
           ≥ 1.5× over cold.
  donate   proof the donated carry is aliased, not defensively copied:
           after a donated dispatch the OLD carry's buffers must be
           deleted (reading them raises), and peak RSS with donation may
           not exceed the copying run's.

Emits BENCH_serve_pipeline.json.
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

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

_REPO = Path(__file__).resolve().parent.parent


def _load_solver_stack():
    """Import JAX and the solver stack into this module's namespace — only
    in a process that runs a case.  The ``--pipeline`` parent stays off JAX
    while its child processes hold the device."""
    global jax, jnp, np, random_measure, GWConfig, Grid1D, GWEngine, \
        GWServeConfig
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import random_measure
    from repro.core import GWConfig
    from repro.core.grids import Grid1D
    from repro.launch.compile_cache import use_compile_cache
    from repro.serve.engine import GWEngine, GWServeConfig

    jax.config.update("jax_enable_x64", True)
    use_compile_cache()

EPS_CYCLE = [5e-2, 2e-2, 8e-3, 2e-3]    # easy → hard, interleaved


def _stream(n, n_req):
    g = Grid1D(n, 1.0 / (n - 1), 1)
    return [(g, g, random_measure(n, 2 * i), random_measure(n, 2 * i + 1),
             EPS_CYCLE[i % len(EPS_CYCLE)]) for i in range(n_req)]


def _run(scheduler, stream, scfg_kwargs, timed=True):
    eng = GWEngine(GWServeConfig(scheduler=scheduler, **scfg_kwargs))
    rids = [eng.submit(gx, gy, mu, nu, eps=eps, eps_init=5e-2)
            for gx, gy, mu, nu, eps in stream]
    t0 = time.perf_counter()
    out = eng.flush()
    jax.block_until_ready([out[r].plan for r in rids])
    wall = time.perf_counter() - t0
    assert set(out) == set(rids)
    if not timed:       # warmup: compile only, skip the metric extraction
        return None, None
    stats = dict(eng.stats)
    outer = [int(out[r].info.outer_iters) for r in rids]
    inner = [int(out[r].info.inner_iters) for r in rids]
    errs = [float(jnp.abs(out[r].plan.sum(1) - s[2]).sum())
            for r, s in zip(rids, stream)]
    return {
        "wall_seconds": wall, "stats": stats,
        "useful_outer_per_request": outer,
        "useful_inner_per_request": inner,
        "max_marginal_err": max(errs),
        "waste_outer": stats["executed_outer"] - stats["useful_outer"],
        "waste_inner": stats["executed_inner"] - stats["useful_inner"],
    }, {r: out[r] for r in rids}


def bench(n, n_req, smoke):
    solver = GWConfig(eps=2e-3,
                      outer_iters=30 if smoke else 60,
                      sinkhorn_iters=200 if smoke else 500)
    scfg = dict(solver=solver, max_batch=4 if smoke else 8,
                size_bucket=n, tol=1e-4, segment_iters=6)
    stream = _stream(n, n_req)

    # warmup: same shapes through both schedulers so the timed flush
    # measures serving, not compilation
    _run("barrier", stream, scfg, timed=False)
    _run("continuous", stream, scfg, timed=False)

    barrier, out_b = _run("barrier", stream, scfg)
    continuous, out_c = _run("continuous", stream, scfg)

    # exactness: scheduling must not change results
    max_plan_diff = 0.0
    counts_equal = True
    for r in out_b:
        max_plan_diff = max(max_plan_diff, float(jnp.abs(
            out_b[r].plan - out_c[r].plan).max()))
        counts_equal &= (int(out_b[r].info.inner_iters)
                         == int(out_c[r].info.inner_iters))

    wall_speedup = barrier["wall_seconds"] / max(continuous["wall_seconds"],
                                                 1e-12)
    exec_inner_ratio = (barrier["stats"]["executed_inner"]
                        / max(continuous["stats"]["executed_inner"], 1))
    out = {
        "backend": jax.default_backend(), "n": n, "n_requests": n_req,
        "eps_cycle": EPS_CYCLE, "serve_cfg": {
            k: v for k, v in scfg.items() if k != "solver"},
        "solver_cfg": {"eps": solver.eps, "outer_iters": solver.outer_iters,
                       "sinkhorn_iters": solver.sinkhorn_iters},
        "barrier": barrier, "continuous": continuous,
        "exactness": {"max_plan_diff": max_plan_diff,
                      "iteration_counts_equal": bool(counts_equal)},
        "summary": {
            "wall_speedup": wall_speedup,
            "executed_inner_ratio": exec_inner_ratio,
            "acceptance": bool(wall_speedup > 1.0 and exec_inner_ratio > 1.0
                               and counts_equal),
        },
    }
    print(f"barrier    wall {barrier['wall_seconds']:.3f}s  executed inner "
          f"{barrier['stats']['executed_inner']:7d} (waste "
          f"{barrier['waste_inner']:6d})", flush=True)
    print(f"continuous wall {continuous['wall_seconds']:.3f}s  executed "
          f"inner {continuous['stats']['executed_inner']:7d} (waste "
          f"{continuous['waste_inner']:6d})  "
          f"refills {continuous['stats']['refills']}", flush=True)
    print(f"→ {wall_speedup:.2f}× wall, {exec_inner_ratio:.2f}× fewer "
          f"executed inner iterations; max plan diff {max_plan_diff:.1e}; "
          f"counts equal: {counts_equal}", flush=True)
    return out


# ---------------------------------------------------------------------------
# --pipeline cases (each runs in its own subprocess)
# ---------------------------------------------------------------------------

def _multi_bucket_stream(sizes, n_req, seed0=0):
    """Round-robin over several grid sizes (→ several buckets) with the
    easy→hard ε cycle inside each: cross-bucket work for the pipeline to
    overlap, mixed difficulty within each bucket."""
    grids = {n: Grid1D(n, 1.0 / (n - 1), 1) for n in sizes}
    out = []
    for i in range(n_req):
        n = sizes[i % len(sizes)]
        out.append((grids[n], grids[n], random_measure(n, seed0 + 2 * i),
                    random_measure(n, seed0 + 2 * i + 1),
                    EPS_CYCLE[(i // len(sizes)) % len(EPS_CYCLE)]))
    return out


def _pipe_flush(scheduler, stream, scfg_kwargs, timed=True):
    eng = GWEngine(GWServeConfig(scheduler=scheduler, **scfg_kwargs))
    rids = [eng.submit(gx, gy, mu, nu, eps=eps, eps_init=5e-2)
            for gx, gy, mu, nu, eps in stream]
    t0 = time.perf_counter()
    out = eng.flush()
    jax.block_until_ready([out[r].plan for r in rids])
    wall = time.perf_counter() - t0
    assert set(out) == set(rids)
    if not timed:
        return None, None
    return {"wall_seconds": wall, "stats": dict(eng.stats)}, out


def _case_stream(smoke: bool) -> dict:
    sizes = [12, 16, 20] if smoke else [32, 48, 64]
    n_req = 6 if smoke else 18
    solver = GWConfig(eps=2e-3, outer_iters=30 if smoke else 60,
                      sinkhorn_iters=200 if smoke else 500)
    scfg = dict(solver=solver, max_batch=4, size_bucket=4, tol=1e-4,
                segment_iters=2, max_inflight_buckets=len(sizes))
    stream = _multi_bucket_stream(sizes, n_req)

    _pipe_flush("continuous", stream, scfg, timed=False)   # compile
    _pipe_flush("pipeline", stream, scfg, timed=False)
    cont, out_c = _pipe_flush("continuous", stream, scfg)
    pipe, out_p = _pipe_flush("pipeline", stream, scfg)

    # result-identical, asserted not assumed: same slot widths per bucket
    # and identical iteration counts; plans to 1e-12 rather than the same
    # bits because the donating dispatch is a SEPARATE XLA executable whose
    # buffer aliasing may reorder a reduction's last ulp (with
    # donate_carries=False the comparison is exactly bitwise — the test
    # suite pins that)
    max_plan_diff = 0.0
    counts_equal = True
    for r in out_c:
        max_plan_diff = max(max_plan_diff, float(jnp.abs(
            out_c[r].plan - out_p[r].plan).max()))
        counts_equal &= (int(out_c[r].info.inner_iters)
                         == int(out_p[r].info.inner_iters))
    assert max_plan_diff <= 1e-12 and counts_equal

    speedup = cont["wall_seconds"] / max(pipe["wall_seconds"], 1e-12)
    # the overlap the pipeline exists for: the serial scheduler leaves the
    # device idle during every harvest's host-side bookkeeping; the
    # pipeline fills those windows with other buckets' dispatches.  On a
    # single-core host that reclaimed idle CANNOT become wall-clock (host
    # bookkeeping and XLA compute share the one core, and concurrent
    # dispatches serialize on the CPU stream), so the ≥1.2× wall gate only
    # binds where the hardware can actually overlap — the idle-reclaim
    # fraction is the machine-independent evidence and is gated everywhere.
    idle_c = cont["stats"]["device_idle_s"]
    idle_p = pipe["stats"]["device_idle_s"]
    reclaimed = (idle_c - idle_p) / max(idle_c, 1e-12)
    ncpu = os.cpu_count() or 1
    accept = bool(reclaimed >= 0.5 and counts_equal
                  and (speedup >= 1.2 if ncpu > 1 else speedup >= 0.9))
    return {
        "case": "stream", "sizes": sizes, "n_requests": n_req,
        "host_cpu_count": ncpu,
        "continuous": cont, "pipeline": pipe,
        "max_plan_diff": max_plan_diff,
        "iteration_counts_equal": bool(counts_equal),
        "max_dispatch_depth": max(pipe["stats"]["dispatch_depth"]),
        "device_idle_reclaimed_frac": reclaimed,
        "wall_speedup": speedup,
        "wall_speedup_gate_applies": bool(ncpu > 1),
        "accept_speedup": accept,
    }


def _case_repeat(smoke: bool) -> dict:
    n = 16 if smoke else 48
    k = 4 if smoke else 8                    # uniques; phase 2 serves 2k
    solver = GWConfig(eps=2e-3, outer_iters=30 if smoke else 60,
                      sinkhorn_iters=200 if smoke else 500)
    scfg = dict(solver=solver, max_batch=4, size_bucket=n, tol=1e-4,
                segment_iters=6, max_inflight_buckets=2)
    uniques = _multi_bucket_stream([n], k, seed0=0)
    fresh = _multi_bucket_stream([n], k, seed0=10_000)
    phase2 = [s for pair in zip(uniques, fresh) for s in pair]  # 50% repeats

    def submit_all(eng, stream):
        return [eng.submit(gx, gy, mu, nu, eps=eps, eps_init=5e-2)
                for gx, gy, mu, nu, eps in stream]

    def timed_flush(eng, rids):
        t0 = time.perf_counter()
        out = eng.flush()
        jax.block_until_ready([out[r].plan for r in rids])
        return time.perf_counter() - t0, out

    cached = GWEngine(GWServeConfig(scheduler="pipeline", cache_capacity=64,
                                    **scfg))
    cold = GWEngine(GWServeConfig(scheduler="pipeline", **scfg))
    assert cold.cache is None
    # phase 1: both engines solve the uniques (cached stores plans; for
    # cold this is also the compile warmup on exactly these shapes)
    submit_all(cached, uniques)
    phase1 = cached.flush()
    submit_all(cold, uniques)
    cold.flush()

    cold_rids = submit_all(cold, phase2)
    cold_wall, _ = timed_flush(cold, cold_rids)
    hot_rids = submit_all(cached, phase2)
    hot_wall, hot_out = timed_flush(cached, hot_rids)

    s = cached.stats
    assert s["cache_hits"] == k              # every repeat answered cached
    # the k hits are bit-identical to phase 1 and cost zero dispatches
    # beyond what the k fresh problems needed: phase2 interleaves
    # (unique_i, fresh_i), so the even positions are the exact repeats,
    # in phase-1 submission order
    for r, pr in zip(hot_rids[0::2], sorted(phase1)):
        np.testing.assert_array_equal(np.asarray(hot_out[r].plan),
                                      np.asarray(phase1[pr].plan))
    throughput = cold_wall / max(hot_wall, 1e-12)
    return {
        "case": "repeat", "n": n, "n_phase2": 2 * k, "repeat_frac": 0.5,
        "cold_wall_seconds": cold_wall, "cached_wall_seconds": hot_wall,
        "cold_dispatches": cold.stats["dispatches"],
        "cached_dispatches": s["dispatches"],
        "cache_hits": s["cache_hits"], "cache_misses": s["cache_misses"],
        "throughput_gain": throughput,
        "accept_throughput": bool(throughput >= 1.5),
    }


def _case_donate(smoke: bool) -> dict:
    from repro.core.gw import (_init_stacked, _segment_stacked_donated,
                               stack_problems)
    from repro.core.solver import SolveControls

    n = 16 if smoke else 64
    solver = GWConfig(eps=5e-2, outer_iters=20, sinkhorn_iters=200)
    cfgk = solver.static_key()
    from repro.core.geometry import as_geometry

    g = as_geometry(Grid1D(n, 1.0 / (n - 1), 1), solver.backend)
    probs = [(g, g, random_measure(n, 7 * i), random_measure(n, 7 * i + 1))
             for i in range(2)]
    ctls = [SolveControls.make(5e-2, 1e-4, 5e-2, 0.5) for _ in probs]
    ops, _, _ = stack_problems(probs, solver, (n, n), ctls, [None, None])
    carry0 = _init_stacked(ops[0], ops[1], ops[2], ops[3], cfgk)
    carry1, _ = _segment_stacked_donated(*ops, carry0, cfgk, 4)
    jax.block_until_ready(carry1.t)
    # the donated input must be CONSUMED — if XLA had fallen back to a
    # defensive copy, carry0 would still be readable
    try:
        np.asarray(carry0.t)
        consumed = False
    except RuntimeError:
        consumed = True
    del carry0

    # peak-RSS cross-check: a donating pipeline flush must not allocate
    # more than the copying one (it reuses the carry buffers in place)
    def flush_rss(donate):
        stream = _multi_bucket_stream([n], 6, seed0=100)
        scfg = dict(solver=solver, max_batch=4, size_bucket=n, tol=1e-4,
                    segment_iters=4, max_inflight_buckets=2,
                    donate_carries=donate)
        _pipe_flush("pipeline", stream, scfg, timed=False)
        _pipe_flush("pipeline", stream, scfg)
        return (resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0)

    rss_donate = flush_rss(True)
    rss_copy = flush_rss(False)          # same process: RSS is cumulative,
    # so donate ≤ copy is implied unless the copying run fits entirely in
    # the donating run's high-water mark — report both, assert the order
    return {
        "case": "donate", "n": n,
        "donated_carry_consumed": bool(consumed),
        "peak_rss_mb_after_donating_flush": rss_donate,
        "peak_rss_mb_after_copying_flush": rss_copy,
        "accept_no_defensive_copy": bool(consumed
                                         and rss_donate <= rss_copy),
    }


_PIPELINE_CASES = {"stream": _case_stream, "repeat": _case_repeat,
                   "donate": _case_donate}


def _spawn_case(name: str, smoke: bool) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(_REPO / "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    cmd = [sys.executable, __file__, "--pipeline", "--case", name]
    if smoke:
        cmd.append("--smoke")
    out = subprocess.run(cmd, capture_output=True, text=True, check=True,
                         cwd=_REPO, env=env)
    return json.loads(out.stdout.strip().splitlines()[-1])


def pipeline_bench(args) -> dict:
    cases = {}
    for name in _PIPELINE_CASES:
        print(f"[serve_bench --pipeline] {name} ...", flush=True)
        cases[name] = _spawn_case(name, args.smoke)
        c = cases[name]
        if name == "stream":
            print(f"    continuous {c['continuous']['wall_seconds']:.3f}s → "
                  f"pipeline {c['pipeline']['wall_seconds']:.3f}s "
                  f"({c['wall_speedup']:.2f}×, depth "
                  f"{c['max_dispatch_depth']}, idle reclaimed "
                  f"{c['device_idle_reclaimed_frac']:.0%}, "
                  f"{c['host_cpu_count']} cpu)", flush=True)
        elif name == "repeat":
            print(f"    cold {c['cold_wall_seconds']:.3f}s → cached "
                  f"{c['cached_wall_seconds']:.3f}s "
                  f"({c['throughput_gain']:.2f}×, {c['cache_hits']} hits)",
                  flush=True)
        else:
            print(f"    carry consumed: {c['donated_carry_consumed']}, "
                  f"peak RSS {c['peak_rss_mb_after_donating_flush']:.0f} → "
                  f"{c['peak_rss_mb_after_copying_flush']:.0f} MB",
                  flush=True)
    return {
        "backend": cases["stream"]["backend"], "smoke": bool(args.smoke),
        "cases": cases,
        "summary": {
            "wall_speedup_vs_continuous": cases["stream"]["wall_speedup"],
            "repeat_throughput_gain": cases["repeat"]["throughput_gain"],
            "acceptance": bool(
                cases["stream"]["accept_speedup"]
                and cases["repeat"]["accept_throughput"]
                and cases["donate"]["accept_no_defensive_copy"]),
        },
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes: execute the serving path in CI")
    ap.add_argument("--n", type=int, default=None, help="grid size")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--pipeline", action="store_true",
                    help="bench the async multi-bucket dispatcher + plan "
                         "cache instead of continuous-vs-barrier")
    ap.add_argument("--case", default=None,
                    help="internal: run ONE --pipeline case in-process and "
                         "print its JSON")
    args = ap.parse_args()
    if args.case:
        _load_solver_stack()
        print(json.dumps({**_PIPELINE_CASES[args.case](args.smoke),
                          "backend": jax.default_backend()}))
        return 0
    if args.pipeline:
        out = pipeline_bench(args)
        dest = args.out or str(_REPO / "BENCH_serve_pipeline.json")
        Path(dest).write_text(json.dumps(out, indent=2) + "\n")
        print(f"wrote {dest}")
        return 0 if out["summary"]["acceptance"] or args.smoke else 1
    _load_solver_stack()
    n = args.n or (16 if args.smoke else 64)
    n_req = args.requests or (6 if args.smoke else 24)
    out = bench(n, n_req, args.smoke)
    dest = args.out or str(_REPO / "BENCH_serve.json")
    Path(dest).write_text(json.dumps(out, indent=2) + "\n")
    print(f"wrote {dest}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
