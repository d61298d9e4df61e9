"""Entropic Gromov-Wasserstein by mirror descent (paper §2.1) with the FGC
fast gradient (paper §3) as the default backend.

Each outer iteration:
    Π   = ∇E(Γ) = C1 − 4·D_X Γ D_Y          (FGC: O(k²MN); dense: O(M²N+MN²))
    Γ   ← Sinkhorn(Π, μ, ν, ε)               (τ = ε, Remark 2.1)
with warm-started log-domain potentials carried across iterations.

The outer loop itself lives in `repro.core.solver.mirror_descent` — the
convergence-controlled driver shared with fgw/ugw/coot and the barycenter.
With ``cfg.tol=0`` (default) it runs exactly ``outer_iters`` steps, the
paper-faithful fixed mode; ``tol>0`` adds tolerance-based early stopping and
(with ``eps_init``) ε-annealing, and every result carries a
`ConvergenceInfo` plus the per-outer-step marginal-error trace.

Either side may be any `repro.core.geometry.Geometry` — uniform grids (FGC
applies), low-rank factored costs, raw point clouds, or explicit dense
matrices; raw Grid1D/Grid2D arguments are adapted with ``cfg.backend``.  All
gradient pieces come from `repro.core.gradient.GradientOperator` (shared
with fgw/ugw/coot).

The solver state is a `repro.core.coupling.Coupling` — the plan
REPRESENTATION is a config axis (``cfg.plan``): "full" carries the dense
(M,N) plan + Sinkhorn potentials (the paper's setting); "lowrank" carries
the factored plan P = Q diag(1/g) Rᵀ of Scetbon et al. (2021) and runs the
whole mirror descent in O((M+N)·(r+cost_rank)) per step — point clouds are
converted to their factored costs (`Geometry.for_factored_plan`) and no
(M,N) array exists anywhere in the solve, which is what admits 10⁵–10⁶
point problems.  Both representations ride the same driver, the same
batched/padded/segmented surfaces below, and the same serving scheduler.

`entropic_gw_batch` solves MANY problems in one vmapped program: every
geometry is padded to a common bucket size with zero-mass support points
(exact under log-domain Sinkhorn — padded potentials pin to −inf, the plan
is identically 0 there), the padded geometries are stacked leaf-wise as
pytrees, and ONE jit-compiled vmap serves the whole batch.  The executable
cache keys on the geometry spec (class/padded size/static params) plus the
cfg's STRUCTURAL fields only — eps/tol/annealing knobs travel as traced
`SolveControls` (stacked per lane, so every request may carry its own
ε/tol/annealing schedule), so retuning them never recompiles.  Under
``tol>0`` each lane early-stops on its own schedule (the driver's
per-problem masking); the batch returns when every lane has converged or
hit the cap.

The batch is also *resumable*: ``max_outer_segment=k`` advances every lane
by at most k outer steps and returns ``(results, resume_state)``; feeding
``resume_state`` back continues bit-identically (the driver's ε/tolerance
schedules are functions of each lane's carried step index).  That segmented
surface — `_init_stacked` / `_segment_stacked` / `stack_problems` /
`_init_lane` — is what `repro.serve.engine.GWEngine` drives as a
continuous-batching scheduler: harvest converged lanes after each segment,
refill the freed slots from the admission queue.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp

from repro import scopes
from repro.core import sinkhorn as sk
from repro.core.coupling import (Coupling, FullCoupling, LowRankCoupling,
                                 coupling_delta, full_init, lowrank_init)
from repro.core.geometry import Geometry, as_geometry
from repro.core.gradient import GradientOperator, LowRankGradientOperator
from repro.core.solver import (ConvergenceInfo, ImplicitSpec, MirrorCarry,
                               SolveControls, fixed_point_value, info_of,
                               init_carry, mirror_descent,
                               mirror_descent_segment, resolve_controls)


@dataclasses.dataclass(frozen=True)
class GWConfig:
    eps: float = 2e-3          # paper §4.1 uses 0.002 (1D) / 0.004 (2D)
    outer_iters: int = 10      # cap; exact count when tol=0 (paper §4.1: 10)
    sinkhorn_iters: int = 200  # inner cap per outer step
    backend: str = "cumsum"    # FGC gradient backend: "scan" (paper-faithful)
    #                            | "cumsum" | "dense" | "pallas"
    sinkhorn_mode: str = "log"
    #: log-mode Sinkhorn dual-update backend: "auto" (fused Pallas kernels
    #: on TPU, XLA scans elsewhere) | "pallas" | "xla".  Structural (part of
    #: the jit cache key, kept by `static_key`); reverse-mode AD never needs
    #: XLA here — the implicit backward pass linearizes its own XLA one-step
    #: map (see `grad_mode`), so any backend is trainable.
    sinkhorn_backend: str = "auto"
    tol: float = 0.0           # early-stop tolerance (0 → fixed-iteration)
    eps_init: float | None = None   # ε-annealing start (None/≤eps → off)
    anneal_decay: float = 0.5  # geometric ε decay per outer step
    sinkhorn_chunk: int = 25   # inner iterations between residual checks
    inner_loosen: float = 1.0  # inner-tol ε-scaling strength (0 → flat tol)
    #: reverse-mode gradient construction (structural): "implicit" = the
    #: envelope term plus the Neumann fixed-point correction from
    #: `repro.core.solver.fixed_point_value` (matches unrolled AD to solver
    #: tolerance); "envelope" = Danskin term only (exact as tol→0, cheaper).
    grad_mode: str = "implicit"
    #: differentiable one-step map shape for the backward pass: Sinkhorn
    #: dual-update pairs per T̃ application (full plan) and Dykstra sweeps
    #: per T̃ application (lowrank — its projection re-walks its duals from
    #: zero, so it needs enough sweeps to re-converge them)
    implicit_inner_steps: int = 1
    implicit_lr_sweeps: int = 25
    #: Neumann-series cap / early-exit threshold for the implicit
    #: correction (∂T̃'s spectral radius approaches 1 as ε shrinks, so the
    #: series needs headroom; the early exit keeps well-conditioned
    #: problems cheap)
    implicit_solve_iters: int = 60
    implicit_solve_tol: float = 1e-10
    #: cost-tile element type for the FUSED kernels ("f32" | "bf16"):
    #: "bf16" streams C (full plan) / the log-kernels (factored plan)
    #: through the MXU-native 16-bit tiles with f32 accumulators — half the
    #: HBM traffic on the dominant operand.  Structural; the XLA expressions
    #: ignore it.
    cost_dtype: str = "f32"
    #: plan representation: "full" (dense (M,N) plan + Sinkhorn potentials)
    #: or "lowrank" (factored P = Q diag(1/g) Rᵀ, Scetbon et al. 2021 —
    #: O((M+N)r) state, no (M,N) array anywhere).  STRUCTURAL: part of the
    #: jit cache key (survives `static_key`) — the two representations are
    #: different programs, not different operand values.
    plan: str = "full"
    #: factored-plan rank r (structural), or "auto": start small and grow
    #: (restart with warm-started zero-blend padded factors) whenever the
    #: Dykstra residual trace stalls without converging, up to
    #: ``plan_rank_max``.  "auto" is a host-level restart driver — one-shot
    #: `entropic_gw`/`entropic_fgw` only; the batched/serving paths need one
    #: static rank per executable and reject it.
    plan_rank: int | str = 16
    plan_rank_max: int = 64    # rank cap for plan_rank="auto" (structural)
    #: explicit cost-factorization rank for `for_factored_plan` conversions
    #: (None keeps exact factorizations — e.g. rank d+2 for sqeuclidean
    #: point clouds; euclidean clouds REQUIRE it for the SVD fallback)
    cost_rank: int | None = None
    #: factored-plan inner-loop backend: "auto" (fused Pallas Dykstra/Gram
    #: kernels on TPU, XLA expressions elsewhere) | "pallas" | "xla" —
    #: resolved by `repro.kernels.ops.resolve_lowrank_backend`, the
    #: factored twin of ``sinkhorn_backend``.  Structural (jit cache key).
    lowrank_backend: str = "auto"
    #: factored-plan factor seeding: "rank2" (the deterministic feasible
    #: rank-2 blend — the default) or "kmeans" (mass-weighted Lloyd
    #: clustering of the support embedding; cuts outer steps on clustered
    #: data).  Structural.
    lowrank_init: str = "rank2"
    #: factored-plan mirror step size γ (value knob: rides in SolveControls,
    #: canonicalized out of the cache key — retuning never recompiles)
    lr_gamma: float = 30.0
    #: floor on the low-rank inner weights g (Dykstra's inequality block).
    #: Structural constant — baked into the executable like iteration caps.
    g_floor: float = 1e-10

    def __post_init__(self):
        if self.plan not in ("full", "lowrank"):
            raise ValueError(
                f"unknown plan {self.plan!r}: expected 'full' or 'lowrank'")
        if self.grad_mode not in ("implicit", "envelope"):
            raise ValueError(
                f"unknown grad_mode {self.grad_mode!r}: expected "
                "'implicit' or 'envelope'")
        if self.cost_dtype not in ("f32", "bf16"):
            raise ValueError(
                f"unknown cost_dtype {self.cost_dtype!r}: expected "
                "'f32' or 'bf16'")
        if isinstance(self.plan_rank, str) and self.plan_rank != "auto":
            raise ValueError(
                f"plan_rank={self.plan_rank!r}: expected an int or 'auto'")
        if self.lowrank_backend not in ("auto", "pallas", "xla"):
            raise ValueError(
                f"unknown lowrank backend {self.lowrank_backend!r}: "
                "expected 'auto', 'pallas', or 'xla'")
        if self.lowrank_init not in ("rank2", "kmeans"):
            raise ValueError(
                f"unknown lowrank init {self.lowrank_init!r}: expected "
                "'rank2' or 'kmeans'")

    def static_key(self) -> "GWConfig":
        """This cfg with the traced value-knobs canonicalized — the jit
        cache key.  eps/tol/eps_init/anneal_decay/lr_gamma reach the solver
        as `SolveControls` operands instead, so retuning them reuses the
        compiled executable.  ``plan``/``plan_rank``/``cost_rank``/
        ``g_floor`` are structural and survive."""
        return dataclasses.replace(self, eps=0.0, tol=0.0, eps_init=None,
                                   anneal_decay=0.0, inner_loosen=0.0,
                                   lr_gamma=0.0)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class GWResult:
    #: dense plan Γ — None for factored-plan solves (use ``coupling``; its
    #: ``.dense()`` materializes on demand for small-problem diagnostics)
    plan: jax.Array | None
    value: jax.Array          # E(Γ): the (squared) GW discrepancy of the plan
    marginal_err: jax.Array
    f: jax.Array | None
    g: jax.Array | None
    #: per-outer-step marginal-error trace (outer_iters,), NaN past the stop
    errs: jax.Array | None = None
    info: ConvergenceInfo | None = None
    #: the plan representation itself (FullCoupling mirrors plan/f/g;
    #: LowRankCoupling carries the Q/R/g factors)
    coupling: Coupling | None = None

    def tree_flatten(self):
        return (self.plan, self.value, self.marginal_err, self.f, self.g,
                self.errs, self.info, self.coupling), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def _result_of(coupling: Coupling, value, marginal_err, errs,
               info) -> GWResult:
    """A GWResult from any plan representation.  Full couplings keep the
    legacy plan/f/g fields populated (aliases of the coupling's leaves, not
    copies); factored plans leave them None."""
    dense = isinstance(coupling, FullCoupling)
    return GWResult(plan=coupling.plan if dense else None, value=value,
                    marginal_err=marginal_err,
                    f=coupling.f if dense else None,
                    g=coupling.g if dense else None,
                    errs=errs, info=info, coupling=coupling)


@jax.named_scope(scopes.VALUE)
def gw_energy(grid_x, grid_y, gamma, backend: str = "cumsum",
              dx2_mu=None, dy2_nu=None):
    """E(Γ) = Σ (d^X_ij − d^Y_pq)² γ_ip γ_jq, via the three-term expansion."""
    return GradientOperator(grid_x, grid_y, backend).energy(
        gamma, dx2_mu, dy2_nu)


def gw_step_fn(op: GradientOperator, c1, mu, nu, cfg: GWConfig):
    """The full-plan GW mirror-descent step closure — the ONE step body
    behind the one-shot solve, the batched solve, and the segmented
    (continuous batching) solve, so all three walk identical iterates.
    State: a `FullCoupling`."""

    def step(state, eps, inner_tol):
        with jax.named_scope(scopes.GRAD):
            cost = op.grad(state.plan, c1)
        with jax.named_scope(scopes.SINKHORN):
            gamma, f, g, err, used = sk.solve_adaptive(
                cost, mu, nu, eps, cfg.sinkhorn_iters, cfg.sinkhorn_chunk,
                inner_tol, cfg.sinkhorn_mode, state.f, state.g,
                backend=cfg.sinkhorn_backend, cost_dtype=cfg.cost_dtype)
        return FullCoupling(gamma, f, g), err, used

    return step


def gw_lr_step_fn(op: LowRankGradientOperator, dx2, dy2, mu, nu,
                  cfg: GWConfig, lr_gamma):
    """The factored-plan step closure: one mirror step on (Q, R, g) — the
    LR-GW gradients at the current factors, KL-prox kernels, and a Dykstra
    projection back onto the coupling polytope (`sinkhorn.lr_mirror_step`).
    The inner caps reuse ``sinkhorn_iters``/``sinkhorn_chunk`` (Dykstra
    sweeps play the Sinkhorn iterations' role in `ConvergenceInfo`), and
    the returned err is the plan's L1 row-marginal gap |P1 − μ|₁ — the same
    residual the full path reports.  ``lr_gamma`` is the traced step size
    (from `SolveControls`); ``eps`` arrives annealed from the driver, so
    ε-schedules work identically across representations."""

    def step(state, eps, inner_tol):
        with jax.named_scope(scopes.GRAD):
            gq, gr, gg = op.grads(state, dx2, dy2, cfg.g_floor)
        with jax.named_scope(scopes.SINKHORN):
            q, r, g, err, used = sk.lr_mirror_step(
                state.q, state.r, state.g, gq, gr, gg, mu, nu, eps,
                lr_gamma, cfg.sinkhorn_iters, cfg.sinkhorn_chunk, inner_tol,
                cfg.g_floor, cfg.lowrank_backend, cost_dtype=cfg.cost_dtype)
        return LowRankCoupling(q, r, g), err, used

    return step


def _static_rank(cfg: GWConfig) -> int:
    if isinstance(cfg.plan_rank, str):
        raise ValueError(
            "plan_rank='auto' adapts the rank with host-level restarts in "
            "the one-shot entropic_gw/entropic_fgw drivers only; the "
            "batched/serving paths need one static plan_rank per compiled "
            "executable")
    return cfg.plan_rank


@jax.named_scope(scopes.INIT)
def gw_init_state(mu, nu, gamma0=None, cfg: GWConfig | None = None,
                  geom_x=None, geom_y=None):
    """The standard cold start as a `Coupling`: product-coupling plan with
    zero-mass-aware potentials (full), or the feasible rank-r factor init
    (lowrank, when ``cfg.plan`` says so — the deterministic rank-2 blend,
    or mass-weighted k-means over the geometry embeddings when
    ``cfg.lowrank_init="kmeans"``; the geometries are only consulted
    there)."""
    if cfg is not None and cfg.plan == "lowrank":
        return lowrank_init(mu, nu, _static_rank(cfg),
                            method=cfg.lowrank_init, geom_x=geom_x,
                            geom_y=geom_y)
    return full_init(mu, nu, gamma0)


def gw_plan_solve(op: GradientOperator, c1, mu, nu, cfg: GWConfig,
                  controls: SolveControls | None = None, state0=None):
    """Convergence-controlled full-plan GW mirror descent on a prepared
    operator — the plan-solve shared by `entropic_gw` and the barycenter's
    inner solves.  ``state0``: optional `FullCoupling` warm start.  Returns
    ``(FullCoupling, ConvergenceInfo)``."""
    ctl = resolve_controls(cfg, controls)
    if state0 is None:
        state0 = gw_init_state(mu, nu)
    step = gw_step_fn(op, c1, mu, nu, cfg)
    return mirror_descent(step, state0, coupling_delta, ctl,
                          cfg.outer_iters)


def gw_plan_segment(op: GradientOperator, c1, mu, nu, cfg: GWConfig,
                    controls: SolveControls, carry: MirrorCarry,
                    segment: int | None = None) -> MirrorCarry:
    """Advance a full-plan GW solve by at most ``segment`` outer steps (see
    `repro.core.solver.mirror_descent_segment`): same step body as
    `gw_plan_solve`, so a segmented solve is bit-identical to an
    uninterrupted one."""
    step = gw_step_fn(op, c1, mu, nu, cfg)
    return mirror_descent_segment(step, coupling_delta, controls,
                                  cfg.outer_iters, carry, segment)


def _implicit_solve(cfg: GWConfig, inputs, controls):
    """`ImplicitSpec.solve` for GW/FGW in either plan representation: the
    exact forward solve the unwrapped solvers ran (same operators, same
    step closures, any backend)."""
    gx, gy, mu, nu, feat, state0 = inputs
    if cfg.plan == "lowrank":
        op = LowRankGradientOperator(gx, gy, cfg.backend, cfg.cost_rank,
                                     cfg.lowrank_backend)
        with jax.named_scope(scopes.INIT):
            dx2, dy2 = op.constant_term(mu, nu)
        if feat is None:
            step = gw_lr_step_fn(op, dx2, dy2, mu, nu, cfg,
                                 controls.lr_gamma)
        else:
            from repro.core import fgw as _fgw
            step = _fgw.fgw_lr_step_fn(op, dx2, dy2, feat ** 2, cfg.theta,
                                       mu, nu, cfg, controls.lr_gamma)
        if state0 is None:
            state0 = gw_init_state(mu, nu, cfg=cfg, geom_x=op.geom_x,
                                   geom_y=op.geom_y)
        return mirror_descent(step, state0, coupling_delta, controls,
                              cfg.outer_iters)
    op = GradientOperator(gx, gy, cfg.backend)
    with jax.named_scope(scopes.INIT):
        c1, _, _ = op.constant_term(mu, nu)
    if state0 is None:
        state0 = gw_init_state(mu, nu)
    if feat is None:
        step = gw_step_fn(op, c1, mu, nu, cfg)
    else:
        from repro.core import fgw as _fgw
        with jax.named_scope(scopes.INIT):
            c2 = (1.0 - cfg.theta) * feat ** 2 + cfg.theta * c1
        step = _fgw.fgw_step_fn(op, c2, cfg.theta, mu, nu, cfg)
    return mirror_descent(step, state0, coupling_delta, controls,
                          cfg.outer_iters)


def _implicit_step(cfg: GWConfig, state, inputs, controls):
    """`ImplicitSpec.step` — ONE differentiable mirror step T̃ at the
    converged state, pure XLA.

    Full plan: rebuild the linearized cost at the plan, run
    ``implicit_inner_steps`` warm-started dual-update pairs (idempotent at
    the solution), reassemble the plan.  Factored plan: the LR gradients +
    prox kernels + ``implicit_lr_sweeps`` differentiable Dykstra sweeps —
    everything (N, r)-sized, so the backward jaxpr carries no (M, N) aval
    for pure GW.  Linearized at the TARGET ε (a converged annealed solve
    has finished its ramp; an unconverged mid-ramp solve's gradient is an
    approximation at ε_target by construction).
    """
    gx, gy, mu, nu, feat, _ = inputs
    eps = controls.eps
    if cfg.plan == "lowrank":
        op = LowRankGradientOperator(gx, gy, cfg.backend, cfg.cost_rank,
                                     "xla")
        dx2, dy2 = op.constant_term(mu, nu)

        def half(state):
            gq, gr, gg = op.grads(state, dx2, dy2, cfg.g_floor)
            if feat is not None:
                # the FGW feature blend of `fgw.fgw_lr_step_fn`
                fsq = feat ** 2
                iq = 1.0 / jnp.maximum(state.g, cfg.g_floor)
                fr = fsq @ state.r
                fq = fsq.T @ state.q
                lin_diag = jnp.sum(state.q * fr, axis=0)
                th = cfg.theta
                gq = th * gq + (1.0 - th) * fr * iq[None, :]
                gr = th * gr + (1.0 - th) * fq * iq[None, :]
                gg = th * gg - (1.0 - th) * (iq ** 2) * lin_diag
            q, r, g = sk.lr_mirror_step_diff(
                state.q, state.r, state.g, gq, gr, gg, mu, nu, eps,
                controls.lr_gamma, cfg.implicit_lr_sweeps, cfg.g_floor)
            return type(state)(q, r, g)

        # T̃ is the DOUBLE mirror step: the factored solver converges to a
        # period-2 orbit in FACTOR space (the plan Q diag(1/g) Rᵀ is exactly
        # fixed, but Dykstra's zero-dual restart leaves (Q, R, g) flipping
        # between two gauge representatives), so the single step has no
        # fixed point to linearize — T̃² does, to machine precision
        return half(half(state))
    op = GradientOperator(gx, gy, cfg.backend)
    c1, _, _ = op.constant_term(mu, nu)
    if feat is None:
        cost = op.grad(state.plan, c1)
    else:
        th = cfg.theta
        c2 = (1.0 - th) * feat ** 2 + th * c1
        cost = c2 - 4.0 * th * op.product(state.plan)
    f, g = sk.sinkhorn_step_diff(cost, mu, nu, eps, state.f, state.g,
                                 cfg.implicit_inner_steps)
    eps = jnp.asarray(eps, mu.dtype)
    plan = jnp.exp((f[:, None] + g[None, :] - cost) / eps)
    return FullCoupling(plan, f, g)


@jax.named_scope(scopes.VALUE)
def _implicit_value(cfg: GWConfig, state, inputs, controls):
    """`ImplicitSpec.value` — the PRIMAL objective, bit-compatible with the
    historical forward expressions (precomputed (D∘D)-applies at (μ, ν) for
    full GW; the cfg's own — possibly fused — factored energy for
    lowrank)."""
    gx, gy, mu, nu, feat, _ = inputs
    if cfg.plan == "lowrank":
        op = LowRankGradientOperator(gx, gy, cfg.backend, cfg.cost_rank,
                                     cfg.lowrank_backend)
        if feat is None:
            return op.energy(state, cfg.g_floor)
        from repro.core import fgw as _fgw
        return _fgw.fgw_lr_value(op, feat ** 2, state, cfg.theta,
                                 cfg.g_floor)
    op = GradientOperator(gx, gy, cfg.backend)
    if feat is None:
        _, dx2_mu, dy2_nu = op.constant_term(mu, nu)
        return op.energy(state.plan, dx2_mu, dy2_nu)
    from repro.core import fgw as _fgw
    return _fgw.fgw_full_value(op, feat, state.plan, cfg.theta)


@jax.named_scope(scopes.VALUE)
def _implicit_value_bwd(cfg: GWConfig, state, inputs, controls):
    """`ImplicitSpec.value_bwd` — the gradient-correct objective for the
    backward pass: the plan's OWN marginals everywhere (E(Γ) depends on μ/ν
    only through the constraint, which the implicit term owns — the primal
    shortcut of substituting (μ, ν) for the marginals would add a spurious
    direct μ-dependence), and the XLA factored energy (the fused Gram-chain
    kernels have no VJP)."""
    gx, gy, mu, nu, feat, _ = inputs
    if cfg.plan == "lowrank":
        op = LowRankGradientOperator(gx, gy, cfg.backend, cfg.cost_rank,
                                     "xla")
        if feat is None:
            return op.energy(state, cfg.g_floor)
        from repro.core import fgw as _fgw
        return _fgw.fgw_lr_value(op, feat ** 2, state, cfg.theta,
                                 cfg.g_floor)
    op = GradientOperator(gx, gy, cfg.backend)
    if feat is None:
        return op.energy(state.plan)
    from repro.core import fgw as _fgw
    return _fgw.fgw_full_value(op, feat, state.plan, cfg.theta)


def implicit_spec(cfg: GWConfig) -> ImplicitSpec:
    """The `ImplicitSpec` for a GW/FGW config — module-level partials over
    the cfg only (hashable, never closing over tracers), so the spec rides
    `fixed_point_value` as its static argument."""
    return ImplicitSpec(solve=partial(_implicit_solve, cfg),
                        step=partial(_implicit_step, cfg),
                        value=partial(_implicit_value, cfg),
                        value_bwd=partial(_implicit_value_bwd, cfg),
                        grad_mode=cfg.grad_mode,
                        solve_iters=cfg.implicit_solve_iters,
                        solve_tol=cfg.implicit_solve_tol)


def entropic_gw(grid_x, grid_y, mu, nu,
                cfg: GWConfig = GWConfig(), gamma0=None,
                controls: SolveControls | None = None) -> GWResult:
    """Entropic GW distance + plan. jit-compatible, and reverse-mode
    differentiable in the geometries, measures, and controls under EVERY
    backend/plan combination: the solve is wrapped in
    `repro.core.solver.fixed_point_value`, whose implicit backward pass is
    built from the converged coupling alone (O(1) solve memory — the
    forward loop is never unrolled or replayed).

    ``grid_x``/``grid_y``: Geometry instances, or raw Grid1D/Grid2D (adapted
    with ``cfg.backend``).  ``controls`` overrides the cfg's traced value
    knobs (eps/tol/eps_init/anneal_decay/lr_gamma) — jitted callers pass it
    as an operand so those values never enter the compilation cache key.

    With ``cfg.plan="lowrank"`` the solve runs entirely on the factored
    representation (result.coupling is a `LowRankCoupling`; plan/f/g are
    None — no (M,N) array is built, so a 10⁵–10⁶-point problem fits), and
    the backward pass stays (N, r)-sized too.  ``gamma0`` warm starts are a
    dense-plan concept and are rejected there.  ``plan_rank="auto"`` keeps
    the host-level restart driver (not differentiable — it branches on
    concrete residuals).
    """
    ctl = resolve_controls(cfg, controls)
    if cfg.plan == "lowrank":
        if gamma0 is not None:
            raise ValueError(
                "gamma0 is a dense-plan warm start; the factored path "
                "resumes from a LowRankCoupling carry instead (see "
                "entropic_gw_batch(resume_state=...))")
        if isinstance(cfg.plan_rank, str):
            return _entropic_gw_lowrank(grid_x, grid_y, mu, nu, cfg, ctl)
        gx = as_geometry(grid_x, cfg.backend)
        gy = as_geometry(grid_y, cfg.backend)
        value, coup, info = fixed_point_value(
            implicit_spec(cfg), (gx, gy, mu, nu, None, None), ctl)
        return _result_of(coup, value, info.marginal_err, info.err_trace,
                          info)
    gx = as_geometry(grid_x, cfg.backend)
    gy = as_geometry(grid_y, cfg.backend)
    state0 = full_init(mu, nu, gamma0) if gamma0 is not None else None
    value, coup, info = fixed_point_value(
        implicit_spec(cfg), (gx, gy, mu, nu, None, state0), ctl)
    return _result_of(coup, value, info.marginal_err, info.err_trace, info)


_AUTO_RANK_START = 8        # plan_rank="auto" first attempt
_AUTO_RANK_BLEND = 0.05     # mass blended into the fresh columns on growth
_AUTO_RANK_WINDOW = 3       # stall lookback (outer steps)
_AUTO_RANK_RATIO = 0.9      # residual must shrink below ratio×lookback


def _residual_stalled(info: ConvergenceInfo) -> bool:
    """Has the Dykstra/marginal residual stopped improving?  True when the
    last outer step's residual recovered less than (1 − ratio) relative to
    ``window`` steps earlier — the signal that the current rank's polytope,
    not the iteration count, is what is binding."""
    import numpy as np
    trace = np.asarray(info.err_trace)
    trace = trace[np.isfinite(trace)]
    if trace.size <= _AUTO_RANK_WINDOW:
        return False
    return bool(trace[-1] > _AUTO_RANK_RATIO
                * trace[-1 - _AUTO_RANK_WINDOW])


def lowrank_descent(step, mu, nu, cfg: GWConfig, ctl: SolveControls,
                    geom_x=None, geom_y=None):
    """Factored-plan mirror descent, shared by GW and FGW: the plain
    convergence-controlled `mirror_descent` at a static ``plan_rank``, or —
    under ``plan_rank="auto"`` — a host-level restart loop that starts at
    rank 8 and doubles (up to ``plan_rank_max``) whenever the solve neither
    converged nor is still making residual progress.  Each restart warm
    starts from the previous factors padded with `LowRankCoupling.pad_rank`
    (a 5% mass blend into the fresh columns keeps the iterate feasible and
    strictly positive where mass lives), so earlier ranks' work is kept.
    The returned `ConvergenceInfo` accumulates outer/inner counts across
    restarts; its trace is the final attempt's.

    "auto" needs concrete residuals between attempts, so it cannot run
    under jit/vmap — geometry-threaded init (``lowrank_init`` k-means
    seeding) works in either mode.
    """
    if not isinstance(cfg.plan_rank, str):
        with jax.named_scope(scopes.INIT):
            state0 = lowrank_init(mu, nu, cfg.plan_rank,
                                  method=cfg.lowrank_init, geom_x=geom_x,
                                  geom_y=geom_y)
        return mirror_descent(step, state0, coupling_delta, ctl,
                              cfg.outer_iters)
    if isinstance(mu, jax.core.Tracer):
        raise ValueError(
            "plan_rank='auto' restarts on concrete residuals and cannot "
            "run under jit/vmap — use a static plan_rank there")
    rank = min(_AUTO_RANK_START, cfg.plan_rank_max)
    state = lowrank_init(mu, nu, rank, method=cfg.lowrank_init,
                         geom_x=geom_x, geom_y=geom_y)
    outer = inner = 0
    while True:
        coup, info = mirror_descent(step, state, coupling_delta, ctl,
                                    cfg.outer_iters)
        outer += int(info.outer_iters)
        inner += int(info.inner_iters)
        if (bool(info.converged) or rank >= cfg.plan_rank_max
                or not _residual_stalled(info)):
            break
        rank = min(2 * rank, cfg.plan_rank_max)
        state = coup.pad_rank(rank, mu, nu, _AUTO_RANK_BLEND)
    info = ConvergenceInfo(jnp.asarray(outer, info.outer_iters.dtype),
                           jnp.asarray(inner, info.inner_iters.dtype),
                           info.marginal_err, info.converged,
                           info.err_trace)
    return coup, info


def _entropic_gw_lowrank(grid_x, grid_y, mu, nu, cfg: GWConfig,
                         ctl: SolveControls) -> GWResult:
    """Factored-plan entropic GW under ``plan_rank="auto"``: the host-level
    rank-growth restart driver (`lowrank_descent`).  Not differentiable —
    it branches on concrete residuals; static ranks route through
    `fixed_point_value` in `entropic_gw` instead."""
    op = LowRankGradientOperator(grid_x, grid_y, cfg.backend, cfg.cost_rank,
                                 cfg.lowrank_backend)
    with jax.named_scope(scopes.INIT):
        dx2, dy2 = op.constant_term(mu, nu)
    step = gw_lr_step_fn(op, dx2, dy2, mu, nu, cfg, ctl.lr_gamma)
    # init sees the CONVERTED geometries (op's factored pair) so one-shot,
    # batched, and padded-lane solves derive k-means seeds from identical
    # embeddings
    coup, info = lowrank_descent(step, mu, nu, cfg, ctl, op.geom_x,
                                 op.geom_y)
    with jax.named_scope(scopes.VALUE):
        value = op.energy(coup, cfg.g_floor)
    return _result_of(coup, value, info.marginal_err, info.err_trace, info)


# ---------------------------------------------------------------------------
# batched solving: many problems, one compiled program
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cfg",))
def _solve_stacked(geoms_x, geoms_y, mus, nus, feats, controls:
                   SolveControls, cfg: GWConfig):
    """vmap core over stacked geometry pytrees.  The jit cache keys on the
    pytree structure — i.e. each side's geometry spec (class, padded size,
    static params) — plus leaf shapes and the cfg's structural fields
    (``cfg`` arrives pre-canonicalized via ``static_key()``; the value
    knobs ride in ``controls``, stacked per lane so every request may carry
    its own ε/tol/annealing schedule).  ``feats`` is None for GW batches or
    a stacked (B, M, N) feature-cost for FGW ones (``cfg`` then carries
    θ as an `FGWConfig`); None vs array changes the operand pytree, so the
    two workloads naturally compile apart."""
    def one(gx, gy, mu, nu, feat, ctl):
        if feat is None:
            return entropic_gw(gx, gy, mu, nu, cfg, controls=ctl)
        from repro.core.fgw import entropic_fgw
        return entropic_fgw(gx, gy, feat, mu, nu, cfg, controls=ctl)

    return jax.vmap(one)(geoms_x, geoms_y, mus, nus, feats, controls)


@partial(jax.jit, static_argnames=("cfg",))
def _init_stacked(geoms_x, geoms_y, mus, nus, cfg: GWConfig) -> MirrorCarry:
    """Fresh stacked carries for a slot batch: cold coupling start per lane
    (product plan or rank-r factors, per ``cfg.plan``; the geometries feed
    the k-means factor seeding when ``cfg.lowrank_init`` asks for it),
    trace sized to the cfg's outer cap."""
    def one(gx, gy, mu, nu):
        return init_carry(gw_init_state(mu, nu, cfg=cfg, geom_x=gx,
                                        geom_y=gy), cfg.outer_iters)

    return jax.vmap(one)(geoms_x, geoms_y, mus, nus)


@partial(jax.jit, static_argnames=("cfg",))
def _init_lane(geom_x, geom_y, mu, nu, cfg: GWConfig) -> MirrorCarry:
    """One UNstacked fresh carry — what the continuous-batching engine
    writes into a freed slot when it admits the next queued request."""
    return init_carry(gw_init_state(mu, nu, cfg=cfg, geom_x=geom_x,
                                    geom_y=geom_y), cfg.outer_iters)


def _segment_stacked_impl(geoms_x, geoms_y, mus, nus, feats,
                          controls: SolveControls, carry: MirrorCarry,
                          cfg: GWConfig, segment: int | None):
    """Advance every lane of a stacked carry by ≤ ``segment`` outer steps
    and return (carry, values) — ``values`` is each lane's GW (or FGW, when
    ``feats`` carries a stacked feature cost) energy at its current plan
    (stable once the lane converges, since its state freezes).

    This is the continuous-batching engine's dispatch unit: the jit cache
    keys on (geometry specs, padded shapes, batch width, segment, structural
    cfg), so a serving stream compiles one executable per bucket × batch
    width and reuses it for every dispatch.  Jitted twice below: the plain
    wrapper (the public segmented-batch surface, where the caller may hold
    on to ``resume_state``) and a carry-DONATING wrapper for the pipelined
    serving scheduler, whose dispatch loop rebinds the carry every segment
    and never reuses the old one — donation lets XLA alias the in/out carry
    buffers, so the harvest/refill cycle is copy-free."""
    def one(gx, gy, mu, nu, feat, ctl, c):
        # constant_term is recomputed per dispatch ON PURPOSE: it is
        # deterministic in (geometry, mu, nu), and evaluating it inside the
        # same vmapped subgraph the uninterrupted _solve_stacked uses is
        # what keeps segmented iterates bit-identical to one-shot solves
        # across separately-compiled programs.  Hoisting it into the init
        # executable would save ~1/(segment·sinkhorn_iters) of a dispatch
        # but let XLA fuse it differently there and break exactness.  The
        # FGW branches below mirror `entropic_fgw`'s one-shot expressions
        # (same step closures, same value assembly) for the same reason.
        if cfg.plan == "lowrank":
            op = LowRankGradientOperator(gx, gy, cfg.backend, cfg.cost_rank,
                                         cfg.lowrank_backend)
            with jax.named_scope(scopes.INIT):
                dx2, dy2 = op.constant_term(mu, nu)
            if feat is None:
                step = gw_lr_step_fn(op, dx2, dy2, mu, nu, cfg,
                                     ctl.lr_gamma)
            else:
                from repro.core import fgw as _fgw
                step = _fgw.fgw_lr_step_fn(op, dx2, dy2, feat ** 2,
                                           cfg.theta, mu, nu, cfg,
                                           ctl.lr_gamma)
            c = mirror_descent_segment(step, coupling_delta, ctl,
                                       cfg.outer_iters, c, segment)
            with jax.named_scope(scopes.VALUE):
                if feat is None:
                    return c, op.energy(c.state, cfg.g_floor)
                from repro.core import fgw as _fgw
                return c, _fgw.fgw_lr_value(op, feat ** 2, c.state,
                                            cfg.theta, cfg.g_floor)
        op = GradientOperator(gx, gy, cfg.backend)
        with jax.named_scope(scopes.INIT):
            c1, dx2_mu, dy2_nu = op.constant_term(mu, nu)
        if feat is None:
            c = gw_plan_segment(op, c1, mu, nu, cfg, ctl, c, segment)
            with jax.named_scope(scopes.VALUE):
                return c, op.energy(c.state.plan, dx2_mu, dy2_nu)
        from repro.core import fgw as _fgw
        with jax.named_scope(scopes.INIT):
            c2 = (1.0 - cfg.theta) * feat ** 2 + cfg.theta * c1
        step = _fgw.fgw_step_fn(op, c2, cfg.theta, mu, nu, cfg)
        c = mirror_descent_segment(step, coupling_delta, ctl,
                                   cfg.outer_iters, c, segment)
        with jax.named_scope(scopes.VALUE):
            return c, _fgw.fgw_full_value(op, feat, c.state.plan, cfg.theta)

    return jax.vmap(one)(geoms_x, geoms_y, mus, nus, feats, controls,
                         carry)


_segment_stacked = jax.jit(_segment_stacked_impl,
                           static_argnames=("cfg", "segment"))
#: the donated twin: identical program, but the carry argument is consumed
#: (its buffers alias the output carry's).  ONLY for callers that rebind —
#: `entropic_gw_batch` must keep the plain wrapper, since its caller may
#: legitimately hold the `resume_state` it passed in.
_segment_stacked_donated = jax.jit(_segment_stacked_impl,
                                   static_argnames=("cfg", "segment"),
                                   donate_argnames=("carry",))


def _pad_to(vec, size: int):
    return jnp.pad(vec, (0, size - vec.shape[0]))


def _stack_side(geoms: Sequence[Geometry], measures, pad: int | None):
    """Validate one side of a batch, pad every geometry to the bucket size,
    and stack (geometry pytrees leaf-wise, measures zero-padded)."""
    for g, m in zip(geoms, measures):
        if m.shape[0] != g.size:
            raise ValueError(
                f"measure length {m.shape[0]} != geometry size {g.size} — "
                "bucket padding would silently absorb the mismatch")
    keys = {g.batch_key() for g in geoms}
    if len(keys) != 1:
        raise ValueError(
            "batch requires compatible geometries per side (one class and "
            f"one set of static params); got keys {sorted(map(str, keys))}")
    sizes = [g.size for g in geoms]
    if not geoms[0].paddable:
        if len(set(sizes)) != 1 or (pad is not None and pad != sizes[0]):
            raise ValueError(
                f"{type(geoms[0]).__name__} batches must be equal-sized")
        n = sizes[0]
    else:
        n = max(sizes) if pad is None else pad
        if n < max(sizes):
            raise ValueError(f"pad_to={pad} < largest problem {max(sizes)}")
    # stack with natural promotion — forcing the measures' dtype here would
    # silently downcast f64 geometry data under f32 measures and break the
    # batch == unbatched-solve guarantee
    stacked_g = jax.tree_util.tree_map(
        lambda *leaves: jnp.stack([jnp.asarray(l) for l in leaves]),
        *[g.pad_to(n) for g in geoms])
    stacked_m = jnp.stack([_pad_to(m, n) for m in measures])
    return stacked_g, stacked_m


def stack_controls(controls, cfg: GWConfig, n: int) -> SolveControls:
    """Per-lane SolveControls for a batch of ``n`` problems, stacked
    leaf-wise.  ``controls`` may be None (every lane gets the cfg's knobs),
    a single SolveControls (shared), or a sequence of exactly ``n``
    per-problem SolveControls — a short list is an error, not a silent
    replication (callers that pad problems, like the serving path's
    duplicate-chunk padding, must pad their controls to match)."""
    if controls is None:
        ctls = [SolveControls.from_config(cfg)] * n
    elif isinstance(controls, SolveControls):
        ctls = [controls] * n
    else:
        ctls = list(controls)
        if len(ctls) != n:
            raise ValueError(
                f"{len(ctls)} controls for {n} problems — per-problem "
                "controls must match the (padded) problem list exactly")
    return jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *ctls)


def _unpack_results(stacked_info, coupling: Coupling, values, errs, gxs,
                    gys, k: int) -> list[GWResult]:
    """Slice per-lane results back to their true (unpadded) sizes.
    ``coupling`` is the stacked (lane-leading) coupling pytree of either
    representation; each lane is indexed out and `slice_to`'d."""
    out = []
    for i in range(k):
        lane = jax.tree_util.tree_map(lambda l, i=i: l[i], coupling)
        info = jax.tree_util.tree_map(lambda l, i=i: l[i], stacked_info)
        out.append(_result_of(lane.slice_to(gxs[i].size, gys[i].size),
                              values[i], stacked_info.marginal_err[i],
                              errs[i], info))
    return out


def _stack_features(features, problems, gxs, gys, m: int, n: int):
    """Stack per-problem FGW feature costs, zero-padded to the bucket
    shape — padded rows/columns meet zero-mass atoms, whose factor/plan
    entries are exactly 0, so the padding never contributes.  ``None``
    (a pure-GW batch) passes through; a mixed batch is an error."""
    if features is None or all(f is None for f in features):
        return None
    if any(f is None for f in features):
        raise ValueError(
            "mixed GW/FGW batches are not supported: features must be all "
            "None or all arrays (serve them as separate buckets)")
    if len(features) != len(problems):
        raise ValueError(
            f"{len(features)} features for {len(problems)} problems")
    feats = []
    for f, gx, gy in zip(features, gxs, gys):
        f = jnp.asarray(f)
        if f.shape != (gx.size, gy.size):
            raise ValueError(
                f"feature cost shape {f.shape} != problem sizes "
                f"({gx.size}, {gy.size})")
        feats.append(jnp.pad(f, ((0, m - f.shape[0]), (0, n - f.shape[1]))))
    return jnp.stack(feats)


def stack_problems(problems: Sequence[tuple], cfg: GWConfig,
                   pad_to: tuple[int, int] | None = None, controls=None,
                   features=None):
    """Pad + stack a problem list into the vmapped solver's operands:
    ``(geoms_x, geoms_y, mus, nus, feats, controls)`` plus the adapted
    per-problem geometries (for slicing results back).  The
    continuous-batching engine uses this to build a slot batch it then
    mutates lane-wise.  ``features``: optional per-problem FGW feature
    costs (see `_stack_features`)."""
    gxs = [as_geometry(p[0], cfg.backend) for p in problems]
    gys = [as_geometry(p[1], cfg.backend) for p in problems]
    if cfg.plan == "lowrank":
        _static_rank(cfg)   # "auto" cannot ride a fixed-shape lane
        # convert BEFORE padding: a padded point cloud would factor its
        # origin-sitting padding atoms into nonzero rows, while padding the
        # factors appends exact zero rows — only the latter keeps padded
        # lanes bit-identical to unpadded solves
        gxs = [g.for_factored_plan(cfg.cost_rank) for g in gxs]
        gys = [g.for_factored_plan(cfg.cost_rank) for g in gys]
    geoms_x, mus_p = _stack_side(gxs, [p[2] for p in problems],
                                 pad_to and pad_to[0])
    geoms_y, nus_p = _stack_side(gys, [p[3] for p in problems],
                                 pad_to and pad_to[1])
    feats = _stack_features(features, problems, gxs, gys, mus_p.shape[1],
                            nus_p.shape[1])
    ctls = stack_controls(controls, cfg, len(problems))
    return (geoms_x, geoms_y, mus_p, nus_p, feats, ctls), gxs, gys


def entropic_gw_batch(problems: Sequence[tuple], cfg: GWConfig = GWConfig(),
                      pad_to: tuple[int, int] | None = None,
                      num_results: int | None = None,
                      controls=None,
                      resume_state: MirrorCarry | None = None,
                      max_outer_segment: int | None = None,
                      features=None):
    """Solve a batch of GW problems ``[(geom_x, geom_y, mu, nu), ...]`` with
    ONE vmapped solver call.  Geometries may be raw Grids (adapted with
    ``cfg.backend``) or any Geometry — low-rank, point-cloud, dense.

    Ragged sizes are padded to the max (or to ``pad_to=(M, N)`` — the
    serving path passes bucketed sizes so repeated batches reuse the same
    compiled executable).  Padded support points carry zero mass, which the
    log-domain Sinkhorn treats exactly (their potentials are −inf, the plan
    is 0 there), so each result matches the unbatched solve on the unpadded
    problem — including its `ConvergenceInfo`: with ``cfg.tol>0`` each lane
    stops on its own iteration count (masked in the shared while_loop), so
    batching changes neither plans nor convergence behaviour.  Per side,
    geometries must share their static params (grid class + exponent ``k``,
    low-rank rank, point dimension + metric) but may differ in traced data
    (spacing ``h``, factors, points) and — when the geometry is paddable —
    in size.  Grid2D problems must be equal-sized (the Kronecker unfolding
    owns the grid axis, so zero-padding the flat axis is not available
    there).

    Returns per-problem GWResults sliced back to their true sizes.
    ``num_results`` limits unpacking to the first so-many problems — the
    serving path pads chunks with duplicate problems to hit power-of-two
    batch shapes, and skips slicing/transferring the duplicates.

    ``controls`` optionally gives every problem its own traced solve knobs
    (see :func:`stack_controls`) — a mixed-difficulty stream runs per-lane
    ε/tol/annealing schedules through ONE executable.

    ``features`` optionally gives every problem an FGW feature-cost matrix
    of shape ``(geom_x.size, geom_y.size)``; ``cfg`` must then be an
    :class:`~repro.core.fgw.FGWConfig` (its ``theta`` weights the feature
    term).  All-None and all-array are the two supported shapes — a mixed
    batch would fork the compiled executable per lane.

    Segmented mode: with ``max_outer_segment=k`` the batch advances at most
    ``k`` outer steps and returns ``(results, resume_state)`` — the results
    reflect the current (possibly unconverged; check ``result.info``)
    state, and passing ``resume_state`` back with the SAME problems
    continues the solve.  A solve split into segments is bit-identical to
    an uninterrupted one (the driver's schedule depends only on the carried
    step index).  ``resume_state`` alone (``max_outer_segment=None``) runs
    the remaining steps to completion.
    """
    segmented = (resume_state is not None) or (max_outer_segment is not None)
    if not problems:
        return ([], None) if segmented else []
    if (features is not None and any(f is not None for f in features)
            and not hasattr(cfg, "theta")):
        raise ValueError(
            "features given but cfg has no feature weight: pass an "
            "FGWConfig (with theta) instead of a GWConfig")
    ops, gxs, gys = stack_problems(problems, cfg, pad_to, controls, features)
    k = len(problems) if num_results is None else num_results
    if not segmented:
        stacked = _solve_stacked(*ops, cfg.static_key())
        return _unpack_results(stacked.info, stacked.coupling,
                               stacked.value, stacked.errs, gxs, gys, k)
    carry = (resume_state if resume_state is not None
             else _init_stacked(ops[0], ops[1], ops[2], ops[3],
                                cfg.static_key()))
    carry, values = _segment_stacked(*ops, carry, cfg.static_key(),
                                     max_outer_segment)
    results = _unpack_results(info_of(carry), carry.state, values,
                              carry.trace, gxs, gys, k)
    return results, carry
