"""``waves`` mixes: closed waves of point-cloud requests through
``GWEngine.serve``.

Each wave's problems are made on the device (from the mix's
``problem_seed`` where it sets one, else from the seed), then handed to
``serve()`` at once; the wave's start is every one of its requests' due
time, and a request's latency runs from there to the moment ``serve()``
yields its result.  The next wave starts when ``serve()`` has returned.
The window runs from the start of the first wave to the end of the last
one it started.  A fresh engine serves the window, so its plan cache
starts empty; warm-up runs on another engine, with problems of its own.

Afterwards a sample of the answered requests, drawn from the seed with up
to ``checks.sample`` per (variant, size), is checked against the reference
(see `WavesDriver.check`).
"""
from __future__ import annotations

import collections
import math
import time
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from bench import generate, roofline
from bench.drivers import Check, Window, span
from bench.reference import gw as ref

def _measure(key, n):
    u = jax.random.uniform(key, (n,), jnp.float32) + 1e-3
    return u / u.sum()


def _cloud_pair(key, problem, n, dim):
    k = jax.random.fold_in(key, problem)
    kx, ky, km, kn = jax.random.split(k, 4)
    return (jax.random.uniform(kx, (n, dim), jnp.float32),
            jax.random.uniform(ky, (n, dim), jnp.float32),
            _measure(km, n), _measure(kn, n))


def _rotate(px, py, rot):
    return px @ rot.T, py @ rot.T


class WavesDriver:
    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro.core.gw import GWConfig
        from repro.serve.engine import GWServeConfig

        self.config, self.traffic, self.seed = config, traffic, seed
        geo = config["geometry"]
        if geo["type"] != "pointcloud":
            raise ValueError(f"waves mixes serve point clouds, not "
                             f"{geo['type']!r}")
        self.sizes = [int(s) for s in geo["sizes"]]
        self.dim = int(geo["dim"])
        self.metric = geo["metric"]
        self.serve_cfg = GWServeConfig(solver=GWConfig(**config["solver"]),
                                       **config["serve"])
        # the window's problems come from the mix's problem_seed, where it
        # sets one; warm-up problems always from the run's seed
        problem_seed = traffic.get("problem_seed", seed)
        self.keys = {
            generate.WINDOW: jax.random.PRNGKey(
                generate.key_words(problem_seed, generate.WINDOW)),
            generate.WARMUP: jax.random.PRNGKey(
                generate.key_words(seed, generate.WARMUP))}
        self.make = jax.jit(_cloud_pair, static_argnames=("n", "dim"))
        self.rotate = jax.jit(_rotate)
        self.pick = generate.rng(seed, 2)
        self.kept = {}
        self._warm_up()

    # -- problems ---------------------------------------------------------

    def problem(self, stream: int, req: generate.Request):
        from repro.core.geometry import PointCloudGeometry

        px, py, mu, nu = self.make(self.keys[stream],
                                   jnp.asarray(req.problem, jnp.int32),
                                   n=req.size, dim=self.dim)
        if req.variant == "rotated":
            px, py = self.rotate(px, py, jnp.asarray(req.rotation,
                                                     jnp.float32))
        return (PointCloudGeometry(px, self.metric),
                PointCloudGeometry(py, self.metric), mu, nu)

    def _engine(self):
        from repro.serve.engine import GWEngine
        return GWEngine(self.serve_cfg)

    def _warm_up(self) -> None:
        """Every bucket at every slot width, then one wave of the cell's
        own mix, on warm-up problems."""
        eng = self._engine()
        widths, w = [], int(self.serve_cfg.max_batch)
        while w >= 1:
            widths.append(w)
            w //= 2
        next_id = 10 ** 6
        for n in self.sizes:
            for w in widths:
                reqs = [generate.Request(next_id + j, n, "first")
                        for j in range(w)]
                next_id += w
                list(eng.serve([self.problem(generate.WARMUP, r)
                                for r in reqs]))
        gen = generate.Waves(self.traffic, self.sizes, self.dim, self.seed,
                             generate.WARMUP)
        list(eng.serve([self.problem(generate.WARMUP, r)
                        for r in gen.wave()]))

    # -- the window -------------------------------------------------------

    def window(self, seconds: float) -> Window:
        eng = self._engine()
        gen = generate.Waves(self.traffic, self.sizes, self.dim, self.seed,
                             generate.WINDOW)
        latencies, failed, attempted, nbytes = [], 0, 0, 0
        stats = collections.Counter()
        chunk = int(self.config["solver"]["sinkhorn_chunk"])
        per_stratum = int(self.config["checks"]["sample"])
        seen_stratum = collections.Counter()
        # id(result) → weak reference: a cache hit yields the object that
        # answered the request it repeats, and did no device work
        answered_before = {}
        t0 = time.perf_counter()
        while True:
            reqs = gen.wave()
            probs = jax.block_until_ready(
                [self.problem(generate.WINDOW, r) for r in reqs])
            first_rid = attempted
            attempted += len(reqs)
            got = {}
            with span("wave"):
                due = time.perf_counter()
                it = eng.serve(probs)
                while True:
                    with span("serve_step"):
                        try:
                            rid, res = next(it)
                        except StopIteration:
                            break
                    got[rid] = (time.perf_counter() - due, res)
            for k, v in eng.stats.items():
                if isinstance(v, (int, float)):
                    stats[k] += v
            failed += len(reqs) - len(got)
            for rid, (lat, res) in got.items():
                req = reqs[rid - first_rid]
                n = req.size
                if (res.plan is None or res.plan.shape != (n, n)
                        or not math.isfinite(float(res.value))):
                    failed += 1
                    continue
                latencies.append(lat)
                seen = answered_before.get(id(res))
                if seen is None or seen() is not res:
                    answered_before[id(res)] = weakref.ref(res)
                    nbytes += roofline.solve_bytes(
                        n, n, int(res.info.outer_iters),
                        int(res.info.inner_iters), chunk)
                stratum = (req.variant, n)
                seen_stratum[stratum] += 1
                slots = self.kept.setdefault(stratum, [])
                if len(slots) < per_stratum:
                    slots.append((req, res))
                else:
                    j = int(self.pick.integers(seen_stratum[stratum]))
                    if j < per_stratum:
                        slots[j] = (req, res)
            answered_before = {k: w for k, w in answered_before.items()
                               if w() is not None}
            if time.perf_counter() - t0 >= seconds:
                break
        window_s = time.perf_counter() - t0
        answered = len(latencies)
        metrics = {"served_rps": answered / window_s}
        if latencies:
            metrics["served_p95_s"] = float(np.percentile(latencies, 95))
        counters = dict(stats, requests=attempted, answered=answered,
                        solved_bytes=nbytes)
        return Window(attempted=attempted, failed=failed,
                      window_s=window_s, metrics=metrics, counters=counters)

    def release(self) -> None:
        """The window's engine, its cache and its lanes went with it."""

    # -- the check --------------------------------------------------------

    def check(self) -> list:
        """Each kept answer against the reference, on the request as sent:

        ``solve_gap``  its value against a cold reference solve of the
                       request, relative: the same optimum, reached;
        ``marginal``   the L1 gaps of its plan's row and column sums to the
                       request's measures, added: the stated accuracy.

        Each number is the largest over the kept answers."""
        limits = self.config["checks"]
        s = ref.Settings.of(self.config["solver"])
        worst = {"solve_gap": 0.0, "marginal": 0.0}
        info = []
        for stratum in sorted(self.kept):
            for req, res in self.kept[stratum]:
                gx, gy, mu, nu = self.problem(generate.WINDOW, req)
                dx = ref.sqeuclidean_distance(gx.points)
                dy = ref.sqeuclidean_distance(gy.points)
                v = float(res.value)
                sol = ref.solve(dx, dy, mu, nu, s)
                got = {"solve_gap": abs(v - sol.value) / abs(sol.value),
                       "marginal": ref.marginal_gap(res.plan, mu, nu)}
                for k, x in got.items():
                    worst[k] = max(worst[k], x) if x == x else math.nan
                info.append(
                    f"request {req.problem} {req.variant} N={req.size}: "
                    f"outer={int(res.info.outer_iters)} "
                    f"inner={int(res.info.inner_iters)} value={v!r} "
                    f"reference outer={sol.outer_iters} "
                    f"inner={sol.inner_iters} value={sol.value!r} "
                    + " ".join(f"{k}={x:.3e}" for k, x in got.items()))
        checks = [Check(k, worst[k], limits[k]) for k in worst]
        return checks, info
