"""Entropic Fused Gromov-Wasserstein (paper Remark 2.2) with FGC gradients.

Objective: (1−θ)·Σ c²_ip γ_ip + θ·E(Γ); gradient C2 − 4θ·D_X Γ D_Y with
C2 = (1−θ)·C⊙C + 2θ·((D_X∘D_X)μ 1ᵀ + 1((D_Y∘D_Y)ν)ᵀ).

Gradient pieces come from `repro.core.gradient.GradientOperator` (shared
with gw/ugw/coot); the outer loop is the shared convergence-controlled
driver `repro.core.solver.mirror_descent` (tol=0 → the paper's fixed
iteration count; tol>0 → early stopping + optional ε-annealing, with a
`ConvergenceInfo` on the result).

The step closures and value assemblies live in module-level helpers
(`fgw_step_fn` / `fgw_lr_step_fn` / `fgw_full_value` / `fgw_lr_value`) so
the batched/segmented drivers in `repro.core.gw` run the EXACT same
expressions as the one-shot solve here — that shared body is what makes
padded serving lanes bit-identical to unbatched FGW solves.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro import scopes
from repro.core import sinkhorn as sk
from repro.core.coupling import FullCoupling, coupling_delta, full_init
from repro.core.geometry import as_geometry
from repro.core.gradient import (GeometryLike, GradientOperator,
                                 LowRankGradientOperator)
from repro.core.gw import (GWConfig, GWResult, _result_of, fixed_point_value,
                           implicit_spec, lowrank_descent)
from repro.core.solver import (SolveControls, mirror_descent,
                               resolve_controls)


@dataclasses.dataclass(frozen=True)
class FGWConfig(GWConfig):
    theta: float = 0.5         # paper §4.1/§4.3 use θ=0.5; §4.4.1 θ=0.1


def fgw_energy(grid_x: GeometryLike, grid_y: GeometryLike, feature_cost,
               gamma, theta,
               backend: str = "cumsum"):
    lin = jnp.sum((feature_cost ** 2) * gamma)
    quad = GradientOperator(grid_x, grid_y, backend).energy(gamma)
    return (1.0 - theta) * lin + theta * quad


def fgw_full_value(op: GradientOperator, feature_cost, gamma, theta):
    """FGW objective at a dense plan, on a prepared operator."""
    lin = jnp.sum((feature_cost ** 2) * gamma)
    return (1.0 - theta) * lin + theta * op.energy(gamma)


def fgw_step_fn(op: GradientOperator, c2, theta, mu, nu, cfg: FGWConfig):
    """The full-plan FGW mirror-descent step closure — same shape as
    `gw.gw_step_fn` but with the blended constant term ``c2 =
    (1−θ)·C⊙C + θ·c1`` and the quadratic gradient scaled by θ.  The ONE
    step body behind the one-shot, batched, and segmented solves."""

    def step(state, eps, inner_tol):
        with jax.named_scope(scopes.GRAD):
            grad = c2 - 4.0 * theta * op.product(state.plan)
        with jax.named_scope(scopes.SINKHORN):
            gamma, f, g, err, used = sk.solve_adaptive(
                grad, mu, nu, eps, cfg.sinkhorn_iters, cfg.sinkhorn_chunk,
                inner_tol, cfg.sinkhorn_mode, state.f, state.g,
                backend=cfg.sinkhorn_backend, cost_dtype=cfg.cost_dtype)
        return FullCoupling(gamma, f, g), err, used

    return step


def fgw_lr_step_fn(op: LowRankGradientOperator, dx2, dy2, fsq, theta,
                   mu, nu, cfg: FGWConfig, lr_gamma):
    """The factored-plan FGW step closure: the LR-GW gradients from
    `LowRankGradientOperator` plus the linear feature term differentiated
    through P = Q diag(1/g) Rᵀ:

        ∂⟨C², P⟩/∂Q = C² R diag(1/g),  ∂/∂R = C²ᵀ Q diag(1/g),
        ∂/∂g = −(1/g²) ⊙ diag(Qᵀ C² R).

    ``fsq`` is the squared feature cost (the solve's ONE (M,N) build);
    each step pays one O(MNr) product against the factors, but the plan
    and all solver state stay factored."""

    def step(state, eps, inner_tol):
        with jax.named_scope(scopes.GRAD):
            gq, gr, gg = op.grads(state, dx2, dy2, cfg.g_floor)
            iq = 1.0 / jnp.maximum(state.g, cfg.g_floor)
            fr = fsq @ state.r       # (M, r)
            fq = fsq.T @ state.q     # (N, r)
            lin_diag = jnp.sum(state.q * fr, axis=0)    # diag(Qᵀ C² R)
            gq = theta * gq + (1.0 - theta) * fr * iq[None, :]
            gr = theta * gr + (1.0 - theta) * fq * iq[None, :]
            gg = theta * gg - (1.0 - theta) * (iq ** 2) * lin_diag
        with jax.named_scope(scopes.SINKHORN):
            q, r, g, err, used = sk.lr_mirror_step(
                state.q, state.r, state.g, gq, gr, gg, mu, nu, eps,
                lr_gamma, cfg.sinkhorn_iters, cfg.sinkhorn_chunk,
                inner_tol, cfg.g_floor, cfg.lowrank_backend,
                cost_dtype=cfg.cost_dtype)
        return type(state)(q, r, g), err, used

    return step


def fgw_lr_value(op: LowRankGradientOperator, fsq, coup, theta, g_floor):
    """FGW objective at a factored plan: linear term contracted through the
    factors (never materializing P) plus the factored GW energy."""
    iq = 1.0 / jnp.maximum(coup.g, g_floor)
    lin = jnp.sum(coup.q * (fsq @ coup.r), axis=0) @ iq
    return (1.0 - theta) * lin + theta * op.energy(coup, g_floor)


def entropic_fgw(grid_x: GeometryLike, grid_y: GeometryLike, feature_cost,
                 mu, nu,
                 cfg: FGWConfig = FGWConfig(), gamma0=None,
                 controls: SolveControls | None = None) -> GWResult:
    """``feature_cost``: (M,N) linear-term cost matrix C (paper's c_ip).
    ``grid_x``/``grid_y``: Grids or any Geometry (grid/low-rank/point-cloud/
    dense) — see repro.core.geometry.

    ``cfg.plan="lowrank"`` runs the factored-plan mirror descent.  The
    feature cost is a user-supplied dense (M,N) input, so FGW cannot be
    fully (M,N)-free: its square is built ONCE per solve and each step pays
    one O(MNr) product against the factors — but the PLAN and all solver
    state stay factored (no new per-iteration (M,N) arrays).

    Reverse-mode differentiable in the geometries, measures, feature cost,
    and controls under every backend/plan combination — the solve routes
    through `repro.core.solver.fixed_point_value` exactly like
    `entropic_gw` (the feature-cost cotangent is inherently (M,N))."""
    ctl = resolve_controls(cfg, controls)
    if cfg.plan == "lowrank":
        if gamma0 is not None:
            raise ValueError("gamma0 is a dense-plan warm start; "
                             "unavailable under plan='lowrank'")
        if isinstance(cfg.plan_rank, str):
            return _entropic_fgw_lowrank(grid_x, grid_y, feature_cost, mu,
                                         nu, cfg, ctl)
        state0 = None
    else:
        state0 = full_init(mu, nu, gamma0) if gamma0 is not None else None
    gx = as_geometry(grid_x, cfg.backend)
    gy = as_geometry(grid_y, cfg.backend)
    value, coup, info = fixed_point_value(
        implicit_spec(cfg), (gx, gy, mu, nu, feature_cost, state0), ctl)
    return _result_of(coup, value, info.marginal_err, info.err_trace, info)


def _entropic_fgw_lowrank(grid_x, grid_y, feature_cost, mu, nu,
                          cfg: FGWConfig, ctl: SolveControls) -> GWResult:
    """Factored-plan FGW through the shared `lowrank_descent` driver —
    same k-means seeding and ``plan_rank="auto"`` growth as factored GW."""
    theta = cfg.theta
    op = LowRankGradientOperator(grid_x, grid_y, cfg.backend, cfg.cost_rank,
                                 cfg.lowrank_backend)
    dx2, dy2 = op.constant_term(mu, nu)
    fsq = feature_cost ** 2      # the ONE per-solve (M,N) build
    step = fgw_lr_step_fn(op, dx2, dy2, fsq, theta, mu, nu, cfg,
                          ctl.lr_gamma)
    coup, info = lowrank_descent(step, mu, nu, cfg, ctl, op.geom_x,
                                 op.geom_y)
    value = fgw_lr_value(op, fsq, coup, theta, cfg.g_floor)
    return _result_of(coup, value, info.marginal_err, info.err_trace, info)
