"""``solve`` mixes: one-shot ``entropic_gw`` solves back to back.

Every seed solves the same work: a pool of ``pool`` measure pairs drawn
once from the mix's ``pool_seed`` at the configuration's shapes, taken in
an order drawn from the seed, a new order for each pass over the pool.
The configuration's geometry is ``bench/geometries/<type>.py``: the
program solves on what its ``program`` returns, the reference on its
dense ``distance``.  Each solve goes through one jitted ``entropic_gw``
compiled during set-up and ends in ``block_until_ready``.  The window
runs from the start of the first solve to the end of the first pass over
the pool that ends after ``seconds``, so every seed solves each pool
problem equally often; ``solve_s`` is the window over the number of
solves.  A solve that returns a value that is not finite, or that stops
at its outer cap short of the configuration's tolerance, has failed.  One
solve, drawn from the seed by reservoir sampling over the window, is kept
and checked against the reference afterwards.
"""
from __future__ import annotations

import math
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from bench import generate, roofline, spec
from bench.drivers import Check, Window, span
from bench.reference import gw as ref

BENCH = Path(__file__).resolve().parents[1]


def _measure(key, n):
    u = jax.random.uniform(key, (n,), jnp.float32) + 1e-3
    return u / u.sum()


def _measures(key, i, n):
    k = jax.random.fold_in(key, i)
    return _measure(jax.random.fold_in(k, 0), n), \
        _measure(jax.random.fold_in(k, 1), n)


def pool_order(seed: int, pool: int):
    """The pool problem of each solve in turn: passes over the whole pool,
    each in an order drawn from the seed."""
    r = generate.rng(seed, generate.WINDOW)
    while True:
        yield from (int(j) for j in r.permutation(pool))


def geometry(geo: dict):
    """The module of ``bench/geometries/<type>.py`` beside this driver's
    own ``bench/`` (the run's checkout) for a configuration's geometry."""
    kind = geo["type"]
    return spec.load_file(BENCH / "geometries" / f"{kind}.py",
                          f"solve geometry of type {kind!r}")


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro.core.gw import GWConfig, entropic_gw

        self.config = config
        self.geometry = geometry(config["geometry"])
        self.grid = self.geometry.program(config["geometry"])
        self.n = self.grid.size
        self.solver = config["solver"]
        cfg = GWConfig(**self.solver)
        grid = self.grid
        self.seed = seed
        self.pool = int(traffic["pool"])
        key = jax.random.PRNGKey(generate.key_words(int(traffic["pool_seed"]),
                                                    generate.WINDOW))
        make = jax.jit(_measures, static_argnames=("n",))
        self.measures = [make(key, j, n=self.n) for j in range(self.pool)]
        self.pick = generate.rng(seed, 2)
        mu = jax.ShapeDtypeStruct((self.n,), jnp.float32)
        self.solve = jax.jit(
            lambda mu, nu: entropic_gw(grid, grid, mu, nu, cfg)
        ).lower(mu, mu).compile()
        jax.block_until_ready(self.measures)
        self.kept = None

    def window(self, seconds: float) -> Window:
        outer, inner, failed, nbytes = [], [], 0, 0
        chunk = int(self.solver["sinkhorn_chunk"])
        order = pool_order(self.seed, self.pool)
        i = 0
        t0 = time.perf_counter()
        while True:
            j = next(order)
            with span("solve"):
                res = jax.block_until_ready(self.solve(*self.measures[j]))
            i += 1
            o, n_in = int(res.info.outer_iters), int(res.info.inner_iters)
            outer.append(o)
            inner.append(n_in)
            nbytes += roofline.solve_bytes(self.n, self.n, o, n_in, chunk)
            if not (math.isfinite(float(res.value))
                    and bool(res.info.converged)):
                failed += 1
            if self.pick.random() < 1.0 / i:
                self.kept = (i - 1, j, res)
            del res
            if i % self.pool == 0 and time.perf_counter() - t0 >= seconds:
                break
        window_s = time.perf_counter() - t0
        return Window(attempted=i, failed=failed, window_s=window_s,
                      metrics={"solve_s": window_s / i},
                      counters={"outer_iters": outer, "inner_iters": inner,
                                "solved_bytes": nbytes})

    def release(self) -> None:
        self.solve = None

    def check(self) -> list:
        """The kept solve judged by the reference: its value against the
        energy of its own plan, that plan's energy against the reference
        solve's optimum, its marginals, and its outer step count."""
        i, j, res = self.kept
        mu, nu = self.measures[j]
        d = self.geometry.distance(self.config["geometry"])
        sol = ref.solve(d, d, mu, nu, ref.Settings.of(self.solver))
        energy = ref.value(d, d, mu, nu, res.plan)
        row, col = ref.marginal_gaps(res.plan, mu, nu)
        v, o = float(res.value), int(res.info.outer_iters)
        limits = self.config["checks"]
        info = (f"solve {i} (pool problem {j}): program outer={o} "
                f"inner={int(res.info.inner_iters)} value={v!r} "
                f"marginal_err={float(res.info.marginal_err)!r} "
                f"plan energy={energy!r} row_gap={row!r} col_gap={col!r}; "
                f"reference outer={sol.outer_iters} inner={sol.inner_iters} "
                f"value={sol.value!r} marginal_err={sol.marginal_err!r} "
                f"plan_l1={ref.l1(res.plan, sol.plan)!r}")
        checks = [
            Check("value_gap", abs(v - energy) / abs(energy),
                  limits["value_gap"]),
            Check("optimum_gap", abs(energy - sol.value) / abs(sol.value),
                  limits["optimum_gap"]),
            Check("marginal_gap", max(row, col), limits["marginal_gap"]),
            Check("outer_gap", abs(o - sol.outer_iters) / sol.outer_iters,
                  limits["outer_gap"]),
        ]
        return checks, [info]
