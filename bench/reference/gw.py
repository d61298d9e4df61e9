"""Plain float32 reference of entropic Gromov-Wasserstein mirror descent.

Written from the method's description (arXiv 2404.08970 §2.1, with the
annealed, tolerance-controlled driver that the served and one-shot solves
state): dense distance matrices, dense products at
``Precision.HIGHEST``, XLA's logsumexp, and host-side loops.  It imports
nothing of the code under test, so a change to the solver cannot move the
yardstick.

One outer step at stage ``s`` with ``eps_s = max(eps, eps_init * decay**s)``:

    C     = 2((D_X∘D_X)μ 1ᵀ + 1 ((D_Y∘D_Y)ν)ᵀ) − 4 D_X Γ D_Y
    f, g  = log-domain Sinkhorn on C at eps_s, warm-started, run in chunks
            of ``chunk`` sweeps until the L1 row-marginal gap is at most
            ``tol * eps_s / eps`` or ``iters`` sweeps were spent
    Γ     = exp((f ⊕ g − C) / eps_s)

The solve stops once the ramp has reached ``eps`` and both the plan's L1
change and the marginal gap are at most ``tol``, or after ``outer`` steps.
While the ramp is still running, a step whose Sinkhorn solve missed its
tolerance keeps the stage (at most ``outer // 2`` such holds).  The value
is E(Γ) = (Γ1)·(D_X∘D_X)μ + (Γᵀ1)·(D_Y∘D_Y)ν − 2⟨Γ, D_X Γ D_Y⟩.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.special import logsumexp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class Settings:
    """The solve as a configuration states it."""

    eps: float
    eps_init: float
    anneal_decay: float
    outer_iters: int
    sinkhorn_iters: int
    sinkhorn_chunk: int
    tol: float

    @classmethod
    def of(cls, solver: dict) -> "Settings":
        eps = float(solver["eps"])
        return cls(eps=eps,
                   eps_init=float(solver.get("eps_init") or eps),
                   anneal_decay=float(solver.get("anneal_decay", 0.5)),
                   outer_iters=int(solver["outer_iters"]),
                   sinkhorn_iters=int(solver["sinkhorn_iters"]),
                   sinkhorn_chunk=int(solver["sinkhorn_chunk"]),
                   tol=float(solver["tol"]))


@dataclasses.dataclass
class Solution:
    plan: jax.Array
    value: float
    outer_iters: int
    inner_iters: int
    marginal_err: float


@partial(jax.jit, static_argnames=("side", "k"))
def grid2d_distance(side: int, k: int) -> jax.Array:
    """(side², side²) Manhattan distances to the power k between the points
    of the unit square's side × side grid, rows in row-major order."""
    h = 1.0 / (side - 1)
    a = jnp.repeat(jnp.arange(side, dtype=F32), side) * h
    b = jnp.tile(jnp.arange(side, dtype=F32), side) * h
    d = jnp.abs(a[:, None] - a[None, :]) + jnp.abs(b[:, None] - b[None, :])
    return d ** k


@jax.jit
def sqeuclidean_distance(points: jax.Array) -> jax.Array:
    """(N, N) squared Euclidean distances, summed coordinate by coordinate
    from exact differences (no Gram matrix, so no matmul precision)."""
    p = points.astype(F32)
    diff = p[:, None, :] - p[None, :, :]
    return jnp.sum(diff * diff, axis=-1)


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


@jax.jit
def _sq_apply(d, w):
    return _mm(d * d, w)


@jax.jit
def _cost(dx, dy, dx2, dy2, plan):
    return 2.0 * (dx2[:, None] + dy2[None, :]) - 4.0 * _mm(_mm(dx, plan), dy)


@partial(jax.jit, static_argnames=("steps",))
def _sinkhorn_chunk(cost, f, g, mu, nu, eps, steps):
    """``steps`` dual-update pairs, then the L1 row-marginal gap."""
    log_mu, log_nu = jnp.log(mu), jnp.log(nu)

    def pair(_, fg):
        f, g = fg
        f = eps * (log_mu - logsumexp((g[None, :] - cost) / eps, axis=1))
        g = eps * (log_nu - logsumexp((f[:, None] - cost) / eps, axis=0))
        return f, g

    f, g = jax.lax.fori_loop(0, steps, pair, (f, g))
    row = jnp.exp((f[:, None] + g[None, :] - cost) / eps).sum(axis=1)
    return f, g, jnp.abs(row - mu).sum()


@jax.jit
def _plan_of(cost, f, g, eps):
    return jnp.exp((f[:, None] + g[None, :] - cost) / eps)


@jax.jit
def _l1(a, b):
    return jnp.abs(a - b).sum()


@jax.jit
def _value(dx, dy, dx2, dy2, plan):
    cross = jnp.sum(plan * _mm(_mm(dx, plan), dy))
    return (_mm(plan.sum(axis=1), dx2) + _mm(plan.sum(axis=0), dy2)
            - 2.0 * cross)


def sinkhorn(cost, mu, nu, eps, iters, chunk, tol, f=None, g=None):
    """Chunked log-domain Sinkhorn: (f, g, sweeps used, L1 row gap)."""
    f = jnp.zeros_like(mu) if f is None else f
    g = jnp.zeros_like(nu) if g is None else g
    eps = jnp.asarray(eps, F32)
    used, err = 0, np.inf
    while used < iters and err > tol:
        steps = min(chunk, iters - used)
        f, g, e = _sinkhorn_chunk(cost, f, g, mu, nu, eps, steps)
        used += steps
        err = float(e)
    return f, g, used, err


def value(dx, dy, mu, nu, plan) -> float:
    """E(Γ) as defined in the module docstring."""
    return float(_value(dx, dy, _sq_apply(dx, mu), _sq_apply(dy, nu), plan))


def solve(dx, dy, mu, nu, s: Settings) -> Solution:
    """The whole annealed mirror descent from the product coupling."""
    mu, nu = mu.astype(F32), nu.astype(F32)
    dx2, dy2 = _sq_apply(dx, mu), _sq_apply(dy, nu)
    plan = mu[:, None] * nu[None, :]
    f, g = jnp.zeros_like(mu), jnp.zeros_like(nu)
    eps = np.float32(s.eps)
    t = stage = inner = 0
    err = np.inf
    dwell_cap = max(s.outer_iters // 2, 1)
    while t < s.outer_iters:
        ramp = np.float32(s.eps_init) * np.float32(s.anneal_decay) ** stage
        ramp_done = ramp <= eps
        eps_t = max(eps, ramp)
        inner_tol = s.tol * float(eps_t / eps)
        cost = _cost(dx, dy, dx2, dy2, plan)
        f, g, used, err = sinkhorn(cost, mu, nu, eps_t, s.sinkhorn_iters,
                                   s.sinkhorn_chunk, inner_tol, f, g)
        new = _plan_of(cost, f, g, jnp.asarray(eps_t, F32))
        del cost
        delta = float(_l1(new, plan))
        plan = new
        hold = (s.tol > 0 and not ramp_done and err > inner_tol
                and t - stage < dwell_cap)
        t += 1
        stage += 0 if hold else 1
        inner += used
        if s.tol > 0 and ramp_done and delta <= s.tol and err <= s.tol:
            break
    return Solution(plan=plan, value=float(_value(dx, dy, dx2, dy2, plan)),
                    outer_iters=t, inner_iters=inner, marginal_err=err)


def l1(a, b) -> float:
    return float(_l1(a.astype(F32), b.astype(F32)))


@jax.jit
def _marginal_gaps(plan, mu, nu):
    p = plan.astype(F32)
    return (jnp.abs(p.sum(axis=1) - mu).sum(),
            jnp.abs(p.sum(axis=0) - nu).sum())


def marginal_gaps(plan, mu, nu) -> tuple:
    """The plan's L1 row-marginal gap to μ and its L1 column-marginal gap
    to ν."""
    row, col = _marginal_gaps(plan, mu.astype(F32), nu.astype(F32))
    return float(row), float(col)


def marginal_gap(plan, mu, nu) -> float:
    """The two gaps of `marginal_gaps`, added."""
    return sum(marginal_gaps(plan, mu, nu))
