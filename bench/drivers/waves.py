"""``waves`` mixes: closed waves of point-cloud requests through
``GWEngine.serve``.

Each wave's problems are made on the device (from the mix's
``problem_seed`` where it sets one, else from the seed; see
`bench.generate`), then handed to ``serve()`` at once; the wave's start
is every one of its requests' due time, and a request's latency runs
from there to the moment ``serve()`` yields its result.  The next wave
starts when ``serve()`` has returned.

The window runs from the start of the first wave to the end of the first
pass of the mix's ``pass_waves`` waves (1 where it sets none) that ends
after ``--seconds``.  Where every seed is sent the same waves, a pass whose
end lies well clear of ``--seconds`` makes every run time the same waves,
where a wave ending close to it would be timed by some runs and not by
others.  A fresh engine serves the window, so its plan cache starts
empty; warm-up runs on another engine, with problems of its own.

Afterwards every answer of one wave of the window, drawn from the seed,
is checked against the reference (see `Driver.check`): a wave holds
every size, and its runs start, refill and repack their slot batches as
the window's do.
"""
from __future__ import annotations

import collections
import itertools
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


#: warm-up lanes' tolerance: above any plan change or marginal gap (both
#: L1 distances of probability measures, at most 2), so a lane converges
#: as soon as its ε ramp ends
_LOOSE = 1e3


def _lane_knobs(eps: float, segment: int, life: int, j: int) -> dict:
    """Solve knobs under which a warm-up lane converges in the ``life``-th
    segment of ``segment`` outer steps after it enters a slot: a ramp of
    k = segment·(life − 1) halvings from eps·2^k ends exactly at ``eps``
    (powers of two are exact), and the loose tolerance holds from there.
    The tolerance differs by ``j`` (a small whole number, exact in
    float32) so that no two lanes share the knobs the plan cache matches
    on, and none is warm-started from another."""
    k = segment * (life - 1)
    return {"eps_init": eps * 2.0 ** k, "anneal_decay": 0.5,
            "tol": _LOOSE + j}


def _slot_width(n: int, max_batch: int) -> int:
    """The slot width `GWEngine` gives a run started with ``n`` requests
    (its rule, restated here so that the benchmark calls no private method
    of the program)."""
    b = 1
    while b < min(n, max_batch):
        b *= 2
    return min(b, max_batch)


def _scripts(max_batch: int) -> list:
    """``(first, fillers, later)`` of each warm-up run of one size: the
    lifetimes (in segments, see `_lane_knobs`) of the lanes the run starts
    with, the number of sliced answers sent after them, and the lifetimes
    of the lanes that join its queue at the next pull.

    ``serve`` pulls ``max_batch`` requests at a time, so the fillers make
    the first pull hold exactly ``first``: the run starts at that width and
    the later lanes arrive while it computes.  For each width b there is
    one run whose first lanes all live two segments, so the later ones
    refill their slots at width b, and one drain for each narrower width:
    all but ``l`` lanes end together with the queue empty, and the run
    repacks to the width of ``l`` lanes.  ``l`` is the fewest lanes that
    repack to that width, so a run with fewer lanes than its width also
    retires the spare slots after a repack; the second drain of a width
    starts with the fewest requests that give that width, so the spare
    slots are retired at the start too.  (The refills need room for the
    later lanes in ``serve``'s backlog, ``max_inflight_buckets`` ×
    ``max_batch``; the default of two buckets leaves it.)"""
    def width(n):
        return _slot_width(n, max_batch)

    out = []
    for b in sorted({width(n) for n in range(1, max_batch + 1)},
                    reverse=True):
        fewest = min(n for n in range(1, max_batch + 1) if width(n) == b)
        lives = [l for l in range(1, b // 2 + 1)
                 if l == 1 or width(l) != width(l - 1)]
        later = _drain(b, lives[0]) if lives else [1]
        out.append(([2] * b, max_batch - b, later))
        for i, l in enumerate(lives[1:]):
            out.append((_drain(fewest if i == 0 else b, l), 0, []))
    return out


def _drain(lanes: int, left: int) -> list:
    """Lifetimes of ``lanes`` lanes of which all but ``left`` end in the
    first segment."""
    return [1] * (lanes - left) + [2] * left


class Driver:
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
        self.kept = []
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
        """Build every engine program the window can run, before it opens.

        What a window runs depends on how its waves fall into each size's
        slot batches: a run starts at any slot width, refills freed slots
        at that width, and repacks to a narrower one as its queue drains.
        Every such program is keyed by shapes alone (bucket size, slot
        width), never by data or by the per-request solve knobs, which
        ride as traced operands.  So warm-up scripts how long each of its
        lanes lives through those knobs (`_lane_knobs`) and serves, through
        `GWEngine.serve` as the window does, one run per script of
        `_scripts` for every size."""
        eng = self._engine()
        sc = self.serve_cfg
        eps = float(sc.solver_cfg().eps)
        segment = max(1, int(sc.segment_iters))
        scripts = _scripts(int(sc.max_batch))
        lanes = itertools.count(1)
        for n in self.sizes:
            def lane(life):
                j = next(lanes)
                prob = self.problem(generate.WARMUP,
                                    generate.Request(j, n, "first"))
                return prob, _lane_knobs(eps, segment, life, j)

            filler = (self.problem(generate.WARMUP,
                                   generate.Request(0, n, "first")),
                      {"service": "sliced"})
            for first, fillers, later in scripts:
                source = ([lane(life) for life in first]
                          + [filler] * fillers
                          + [lane(life) for life in later])
                list(eng.serve(source))

    # -- the window -------------------------------------------------------

    def window(self, seconds: float) -> Window:
        eng = self._engine()
        gen = generate.Waves(self.traffic, self.sizes, self.dim, self.seed,
                             generate.WINDOW)
        latencies, failed, attempted, nbytes = [], 0, 0, 0
        stats = collections.Counter()
        chunk = int(self.config["solver"]["sinkhorn_chunk"])
        pass_waves = int(self.traffic.get("pass_waves", 1))
        # id(result) → weak reference: a cache hit yields the object that
        # answered the request it repeats, and did no device work
        answered_before = {}
        t0 = time.perf_counter()
        for wave_no in itertools.count():
            reqs = gen.wave()
            # a wave replaces the kept one with chance 1/(wave_no + 1), so
            # each wave of the window is the checked one with equal chance
            keep = (wave_no == 0
                    or int(self.pick.integers(wave_no + 1)) == 0)
            if keep:
                self.kept = []
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
                if keep:
                    self.kept.append((req, res))
            answered_before = {k: w for k, w in answered_before.items()
                               if w() is not None}
            if (time.perf_counter() - t0 >= seconds
                    and (wave_no + 1) % pass_waves == 0):
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
        """Each answer of the kept wave against the reference, on the
        request as sent:

        ``solve_gap``  its value against a cold reference solve of the
                       request, relative: the same optimum, reached;
        ``marginal``   the L1 gaps of its plan's row and column sums to the
                       request's measures, added: the stated accuracy.

        Each number is the largest over the kept answers."""
        limits = self.config["checks"]
        s = ref.Settings.of(self.config["solver"])
        worst = {"solve_gap": 0.0, "marginal": 0.0}
        info = []
        for req, res in self.kept:
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
