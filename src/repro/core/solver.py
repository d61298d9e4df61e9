"""Convergence-controlled mirror-descent driver — the single outer loop
behind every solver in this repo (gw, fgw, ugw, coot, and the barycenter's
inner plan solves).

The paper's §4.1 experiments run blind fixed-iteration loops (10 outer ×
200 Sinkhorn sweeps).  That is a *reproduction* setting, not a serving
policy: easy problems burn ~20× the sweeps they need, hard ones silently
return non-converged plans.  Following Rioux et al. (2023, *Entropic
Gromov-Wasserstein Distances: Stability and Algorithms*) the driver makes
convergence tolerance-dependent, and following Scetbon et al. (2021) it
supports ε-annealing, which is what makes the paper's ε=0.002 regime cheap:

  * **Early stopping** — a bounded ``lax.while_loop`` over outer steps,
    stopping when the plan's L1 change and the inner solver's residual both
    fall under ``tol``.  ``tol=0`` reproduces the fixed-iteration mode
    exactly (the loop runs to its cap; the criterion can never fire).
  * **Per-problem masking** — the loop carry is explicitly select-masked
    with each problem's own "still active" predicate, so under ``vmap`` a
    batch runs until every real lane converged while converged lanes commit
    no further dual updates: their plan, potentials, counters, and traces
    freeze (compute is still spent on them until the whole batch finishes —
    vmap lanes execute in lockstep).
  * **ε-annealing** — the outer step at index t runs at
    ``eps_t = max(eps, eps_init · decay^t)`` with warm-started potentials
    carried across stages; convergence is only declared once the schedule
    has reached the target ε.
  * **ConvergenceInfo** — outer/inner iterations actually executed, the
    final residual, a converged flag, and the full per-outer-step residual
    trace (NaN past the stopping point), threaded into ``GWResult`` and
    per-request through ``GWEngine.flush``.
  * **Resumability** — the loop's whole carry (solver state, step counter,
    inner-iteration tally, residual, converged flag, error trace) is an
    explicit ``MirrorCarry`` pytree.  ``mirror_descent_segment`` runs at
    most ``segment`` more outer steps on a carry and returns the advanced
    carry, so a solve can be split into bounded segments and resumed —
    bit-identically, because the segment body is the same step sequence the
    uninterrupted loop runs and every schedule quantity (ε_t, inner
    tolerance) is a function of the carried global step index, not of
    wall-clock position in any one dispatch.  This is what lets
    ``GWEngine`` harvest converged lanes between segments and refill their
    slots (continuous batching) without changing any lane's result.
  * **Stage-dependent inner tolerance** — each outer step's inner Sinkhorn
    solve targets ``controls.inner_tol_at(t)``: proportional to the current
    annealed ε while the schedule ramps (classic ε-scaling — there is no
    point polishing duals that the next, sharper ε will invalidate) and
    exactly ``tol`` once the target ε is reached.  ``inner_loosen`` (traced,
    default 1) interpolates back to the flat schedule at 0.

All knobs that are *values* (eps, tol, eps_init, anneal_decay,
inner_loosen) live in ``SolveControls``, a pytree of traced scalars: jitted
callers take them as operands, so retuning the tolerance or the schedule
NEVER recompiles.  Structural knobs (iteration caps, chunk sizes, backends
— including the inner Sinkhorn dual-update backend, which may route each
step's sweeps through the fused Pallas kernels) stay static on the configs;
because ε reaches the Pallas kernels as a traced operand too, ε-annealing
across stages reuses one executable under either backend.

Reverse-mode differentiation is NOT a separate loop mode: every solve runs
the while_loop driver, and :func:`fixed_point_value` wraps it in a
``jax.custom_vjp`` whose backward pass is built from the converged state
alone — the envelope gradient of the objective plus an implicit
(fixed-point) correction obtained by linearizing ONE differentiable mirror
step at the solution.  The forward pass may therefore run any backend
(fused Pallas kernels included) and any plan representation; the backward
pass replays only the one-step map, so reverse memory is O(1) in the
iteration counts.  The historical ``unroll=True`` scan path is gone.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro import scopes


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class SolveControls:
    """Traced solve knobs: values, never jit cache keys.

    ``tol=0`` disables early stopping; ``eps_init <= eps`` disables
    annealing.  Build with :meth:`make` / :meth:`from_config` so Python
    floats become scalar arrays (traced operands under jit).
    """

    eps: jax.Array          # target ε
    tol: jax.Array          # convergence tolerance (0 → fixed-iteration)
    eps_init: jax.Array     # annealing start (≤ eps → no annealing)
    anneal_decay: jax.Array  # geometric decay factor per outer step
    inner_loosen: jax.Array  # inner-tol ε-scaling strength (0 → flat tol)
    lr_gamma: jax.Array     # factored-plan mirror step size (plan="lowrank")

    @classmethod
    def make(cls, eps, tol=0.0, eps_init=None, anneal_decay=0.5,
             inner_loosen=1.0, lr_gamma=30.0):
        ft = jnp.result_type(float)
        return cls(eps=jnp.asarray(eps, ft), tol=jnp.asarray(tol, ft),
                   eps_init=jnp.asarray(eps if eps_init is None else eps_init,
                                        ft),
                   anneal_decay=jnp.asarray(anneal_decay, ft),
                   inner_loosen=jnp.asarray(inner_loosen, ft),
                   lr_gamma=jnp.asarray(lr_gamma, ft))

    @classmethod
    def from_config(cls, cfg):
        """From any config carrying eps/tol/eps_init/anneal_decay fields
        (``inner_loosen``/``lr_gamma`` are optional — configs without them
        get the default ε-scaled inner-tolerance schedule and the default
        factored-plan step size)."""
        return cls.make(cfg.eps, cfg.tol, cfg.eps_init, cfg.anneal_decay,
                        getattr(cfg, "inner_loosen", 1.0),
                        getattr(cfg, "lr_gamma", 30.0))

    def eps_at(self, t):
        """Annealed ε for outer step ``t``: max(eps, eps_init · decay^t)."""
        ramp = self.eps_init * self.anneal_decay ** t.astype(self.eps.dtype)
        return jnp.maximum(self.eps, ramp)

    def anneal_done(self, t):
        """True once step ``t`` runs at the target ε (convergence may only
        be declared from here on — the plan still moves while ε decays)."""
        ramp = self.eps_init * self.anneal_decay ** t.astype(self.eps.dtype)
        return ramp <= self.eps

    def inner_tol_at(self, t):
        """Inner-solver tolerance for outer step ``t`` (ε-scaling): the
        inner Sinkhorn solve at an annealed eps_t > eps targets
        ``tol · (eps_t/eps)`` — duals solved under a provisional ε get
        invalidated by the next decay stage, so polishing them past the
        stage's own scale is wasted work — and exactly ``tol`` once the
        schedule reaches the target ε.  ``inner_loosen`` interpolates:
        0 restores the flat schedule, 1 (default) is full ε-scaling.
        ``tol=0`` (fixed mode) stays 0 everywhere."""
        ratio = self.eps_at(t) / self.eps
        return self.tol * (1.0 + self.inner_loosen * (ratio - 1.0))

    def tree_flatten(self):
        return (self.eps, self.tol, self.eps_init, self.anneal_decay,
                self.inner_loosen, self.lr_gamma), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ConvergenceInfo:
    """What a solve actually did — the serving path's convergence signal."""

    outer_iters: jax.Array   # int32: outer mirror-descent steps executed
    inner_iters: jax.Array   # int32: total inner (Sinkhorn) iterations
    marginal_err: jax.Array  # residual after the last executed step
    converged: jax.Array     # bool: tol reached before the cap (False at tol=0)
    err_trace: jax.Array     # (outer_cap,) residual per step; NaN past stop

    def tree_flatten(self):
        return (self.outer_iters, self.inner_iters, self.marginal_err,
                self.converged, self.err_trace), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class MirrorCarry:
    """The driver's complete resumable state: everything one outer solve
    needs to continue exactly where it left off.  ``state`` is the solver's
    own pytree — for GW a `repro.core.coupling.Coupling` (dense plan + warm
    duals, or low-rank factors Q/R/g), for ugw/coot their tuple states; the
    rest are the driver's counters.  A carry advanced ``segment`` steps at a time through
    ``mirror_descent_segment`` visits the same iterates, bit for bit, as one
    uninterrupted run — ε-annealing and the inner-tolerance schedule depend
    only on the carried ``t``.

    Because the whole loop state is this one pytree, a segmented dispatch
    can DONATE it (``jax.jit(..., donate_argnames=("carry",))``): the input
    and output carries have identical shapes/dtypes, so XLA aliases the
    buffers and the refill-scatter/segment cycle runs copy-free.  A donated
    carry is consumed — callers must rebind to the returned carry and never
    touch the old reference again (its buffers are deleted)."""

    state: object            # solver state pytree (plan, duals, ...)
    t: jax.Array             # int32: outer steps executed so far
    stage: jax.Array         # int32: annealing-schedule position (≤ t)
    inner: jax.Array         # int32: total inner iterations so far
    err: jax.Array           # residual after the last executed step
    done: jax.Array          # bool: converged (never set under tol=0)
    trace: jax.Array         # (outer_cap,) per-step residual; NaN past t

    def dispatch_ready(self) -> bool:
        """True once every buffer of this carry has materialized — i.e. the
        async dispatch that produced it has finished on the device.  The
        pipelined serving scheduler polls this to harvest completed bucket
        segments without blocking on the ones still computing (JAX arrays
        are futures under async dispatch; ``is_ready`` never blocks)."""
        return all(leaf.is_ready()
                   for leaf in jax.tree_util.tree_leaves(self)
                   if hasattr(leaf, "is_ready"))

    def tree_flatten(self):
        return (self.state, self.t, self.stage, self.inner, self.err,
                self.done, self.trace), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.named_scope(scopes.INIT)
def init_carry(state0, outer_cap: int) -> MirrorCarry:
    """A fresh carry: no steps taken, trace all-NaN, not converged."""
    ft = jnp.result_type(float)
    zero = jnp.zeros((), jnp.int32)
    return MirrorCarry(state=state0, t=zero, stage=zero, inner=zero,
                       err=jnp.asarray(jnp.inf, ft),
                       done=jnp.zeros((), bool),
                       trace=jnp.full((outer_cap,), jnp.nan, ft))


def info_of(carry: MirrorCarry) -> ConvergenceInfo:
    """The carry's driver counters as the public convergence signal."""
    return ConvergenceInfo(outer_iters=carry.t, inner_iters=carry.inner,
                           marginal_err=carry.err, converged=carry.done,
                           err_trace=carry.trace)


def resolve_controls(cfg, controls: SolveControls | None = None):
    """Traced controls built from ``cfg`` unless given explicitly.

    Every solver runs the same while_loop driver: reverse-mode
    differentiation happens through :func:`fixed_point_value`'s implicit
    backward pass, not through a loop-structure choice, so there is no
    mode decision to make here anymore.
    """
    return SolveControls.from_config(cfg) if controls is None else controls


def plan_delta(new_state, old_state):
    """L1 change of the transport plan between outer steps, for states whose
    first element is the plan (gw/fgw/ugw convention)."""
    return jnp.abs(new_state[0] - old_state[0]).sum()


@jax.named_scope(scopes.DRIVER)
def mirror_descent_segment(step_fn, delta_fn, controls: SolveControls,
                           outer_cap: int, carry: MirrorCarry,
                           segment: int | None = None) -> MirrorCarry:
    """Advance a solve by at most ``segment`` outer steps (all remaining
    steps when ``segment`` is None) and return the new carry.

    ``step_fn(state, eps_t, inner_tol) -> (new_state, err, inner_iters)``
    performs one mirror-descent step at the annealed ``eps_t``: build the
    linearized cost, solve the entropic-OT subproblem to the stage's
    ``inner_tol``, return the inner solver's residual and the number of
    inner iterations it used.  ``delta_fn(new_state, old_state)`` measures
    the plan's L1 movement.

    Convergence (per problem): annealing finished AND plan movement ≤ tol
    AND inner residual ≤ tol — strict ``tol > 0`` gating means ``tol=0``
    runs exactly ``outer_cap`` steps (the paper-faithful fixed mode).

    Segmenting changes nothing but the dispatch granularity: every schedule
    quantity is a function of the carried ``stage``/``t`` counters, and the
    body is the identical step sequence, so N segments of k steps reproduce
    one run of N·k steps bit-for-bit.  That exactness is what the
    continuous-batching engine's harvest-and-refill loop relies on.

    **Annealing stage clock.** Schedule quantities (ε_t, the inner
    tolerance) are read at the carried ``stage`` counter, not the raw step
    counter ``t``.  The stage advances with every step *whose inner solve
    actually reached its stage tolerance* — when the inner Sinkhorn solve
    caps out mid-ramp (``step_err > inner_tol_at(stage)``), the stage
    holds, so the next outer step retries at the same ε instead of
    sharpening an already-unconverged subproblem.  Deep ramps
    (eps_init/eps spanning many stages at small final ε) otherwise leave
    the solve permanently behind its own schedule and the residual
    oscillates without converging.  Whenever every inner solve converges
    within its caps — all shallow-ramp and non-annealed runs — ``stage``
    equals ``t`` and the iterates are bit-identical to the un-clocked
    driver; dwell is also disabled under ``tol=0`` (fixed mode) and
    bounded overall by ``outer_cap // 2`` extra steps.

    The loop runs under the ``gw.driver`` scope and the plan change under
    ``gw.delta``; ``step_fn`` names its own stages (`repro.scopes`).
    """
    t_end = (jnp.asarray(outer_cap, jnp.int32) if segment is None
             else jnp.minimum(jnp.asarray(outer_cap, jnp.int32),
                              carry.t + segment))
    dwell_cap = jnp.asarray(max(outer_cap // 2, 1), jnp.int32)

    def cond(c):
        return (c.t < t_end) & jnp.logical_not(c.done)

    def body(c):
        # per-problem masking: under vmap a converged (or segment-finished)
        # lane keeps entering the body while siblings run, but commits NO
        # update — its plan, duals, counters, and trace all freeze.  JAX's
        # while_loop batching rule already select-masks the carry by each
        # lane's own cond (the inner _chunked_loop relies on exactly that);
        # the explicit mask here states the invariant in code rather than
        # leaning on the batching rule alone.
        active = jnp.logical_not(c.done) & (c.t < t_end)
        inner_tol = controls.inner_tol_at(c.stage)
        new_state, step_err, used = step_fn(c.state,
                                            controls.eps_at(c.stage),
                                            inner_tol)
        with jax.named_scope(scopes.DELTA):
            delta = delta_fn(new_state, c.state)
        conv = ((controls.tol > 0.0) & controls.anneal_done(c.stage)
                & (delta <= controls.tol) & (step_err <= controls.tol))
        # hold the annealing stage while the inner solver is capped out
        # mid-ramp; (t - stage) counts holds already spent, bounding dwell.
        hold = ((controls.tol > 0.0)
                & jnp.logical_not(controls.anneal_done(c.stage))
                & (step_err > inner_tol)
                & ((c.t - c.stage) < dwell_cap))
        state = jax.tree_util.tree_map(
            lambda n, o: jnp.where(active, n, o), new_state, c.state)
        return MirrorCarry(
            state=state,
            t=jnp.where(active, c.t + 1, c.t),
            stage=jnp.where(active & jnp.logical_not(hold),
                            c.stage + 1, c.stage),
            inner=jnp.where(active, c.inner + used, c.inner),
            err=jnp.where(active, step_err.astype(c.err.dtype), c.err),
            done=c.done | (active & conv),
            trace=jnp.where(active, c.trace.at[c.t].set(step_err), c.trace))

    return jax.lax.while_loop(cond, body, carry)


def mirror_descent(step_fn, state0, delta_fn, controls: SolveControls,
                   outer_cap: int):
    """Run ``step_fn`` to convergence (or to ``outer_cap``).

    One-shot front end over :func:`mirror_descent_segment` — see its
    docstring for the step contract and the convergence criterion.

    Returns ``(final_state, ConvergenceInfo)``.
    """
    carry = mirror_descent_segment(step_fn, delta_fn, controls, outer_cap,
                                   init_carry(state0, outer_cap))
    return carry.state, info_of(carry)


# ---------------------------------------------------------------------------
# The implicit-differentiation surface.
#
# Entropic GW gradients do not need unrolled loops: by the envelope /
# Danskin argument (Rioux, Goldfeld & Kato 2023) the derivative of the
# entropic value depends only on the converged plan, and for loose
# tolerances the residual sensitivity is recovered by the implicit function
# theorem applied to the mirror-descent fixed point s* = T(s*, θ).  For any
# downstream function F(s*, θ),
#
#   dF/dθ = ∂θF + (∂θT)ᵀ u,     u = (I − ∂sTᵀ)⁻¹ w,     w = ∂sF-cotangent,
#
# where u is computed by a Neumann series u = Σₖ (∂sTᵀ)ᵏ w — each term is
# one VJP of the *one-step* map at the converged state, so reverse memory
# is O(1) in the forward iteration count and the forward solve can run any
# backend (fused Pallas kernels included): only `step` below must be
# differentiable, never the solve loop itself.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ImplicitSpec:
    """Static description of one differentiable fixed-point problem.

    All callables must be module-level functions or ``functools.partial``
    closures over *static* configuration only (never traced values) — the
    spec rides through ``jax.custom_vjp`` as a non-differentiable argument.

    - ``solve(inputs, controls) -> (state, info)``: the full solve, free to
      use any backend / while_loop / Pallas kernel.
    - ``step(state, inputs, controls) -> state``: ONE differentiable
      application of the fixed-point map T̃ at the solution (XLA ops only);
      linearized by the backward pass.  At a converged state it must be
      (approximately) idempotent.
    - ``value(state, inputs, controls) -> scalar``: the primal objective
      reported forward (bit-compatible with the historical expressions).
    - ``value_bwd``: optional gradient-correct replacement for ``value``
      used only in the backward pass (e.g. the XLA energy expression when
      the forward value came from a fused kernel without a VJP).
    - ``grad_mode``: ``"implicit"`` (envelope + Neumann fixed-point
      correction) or ``"envelope"`` (Danskin term only — exact in the
      tol→0 limit, cheaper, skips the correction).
    - ``solve_iters`` / ``solve_tol``: Neumann series cap and early-exit
      threshold on the L1 norm of the latest term.
    """

    solve: Callable
    step: Callable
    value: Callable
    value_bwd: Optional[Callable] = None
    grad_mode: str = "implicit"
    solve_iters: int = 30
    solve_tol: float = 1e-10


def _is_float0(x) -> bool:
    return getattr(x, "dtype", None) == jax.dtypes.float0


def _add_cotangents(a, b):
    """Leafwise sum of two cotangent pytrees, preserving float0 leaves
    (integer-valued primals carry no gradient)."""
    def add(x, y):
        if _is_float0(x):
            return x if _is_float0(y) else y
        if _is_float0(y):
            return x
        return x + y
    return jax.tree_util.tree_map(add, a, b)


def _ct_l1(tree):
    """L1 mass of a cotangent pytree (float0 leaves contribute nothing)."""
    total = jnp.zeros((), jnp.result_type(float))
    for leaf in jax.tree_util.tree_leaves(tree):
        if not _is_float0(leaf):
            total = total + jnp.abs(leaf).sum()
    return total


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def fixed_point_value(spec: ImplicitSpec, inputs, controls):
    """Solve the fixed point described by ``spec`` and return
    ``(value, state, info)`` — reverse-mode differentiable in ``inputs``
    and ``controls`` through the implicit backward pass, regardless of how
    ``spec.solve`` runs forward.

    When not differentiated this is exactly ``spec.solve`` +
    ``spec.value`` — ``jax.custom_vjp`` is the identity on the primal
    path, so forward results are bit-identical to the unwrapped solver.
    """
    state, info = spec.solve(inputs, controls)
    value = spec.value(state, inputs, controls)
    return value, state, info


def _fpv_fwd(spec, inputs, controls):
    state, info = spec.solve(inputs, controls)
    value = spec.value(state, inputs, controls)
    return (value, state, info), (state, inputs, controls)


def _fpv_bwd(spec, res, cts):
    state, inputs, controls = res
    ct_value, ct_state, _ct_info = cts

    # stop any residual tracer linkage: the backward pass linearizes at the
    # *converged* state, treated as a point, exactly as the envelope/IFT
    # argument prescribes.
    state = jax.lax.stop_gradient(state)

    val_fn = spec.value_bwd if spec.value_bwd is not None else spec.value
    _, vjp_val = jax.vjp(val_fn, state, inputs, controls)
    dv_s, dv_x, dv_c = vjp_val(ct_value)

    # cotangent entering the fixed point: from the value plus any direct
    # cotangent on the returned state (e.g. a loss reading the plan).
    w = _add_cotangents(dv_s, ct_state)

    if spec.grad_mode == "envelope":
        return dv_x, dv_c

    # u = Σₖ (∂sT̃ᵀ)ᵏ w by Neumann iteration with early exit; one jax.vjp
    # of the one-step map stores its residuals once, each series term is a
    # single transpose application.
    _, vjp_state = jax.vjp(lambda s: spec.step(s, inputs, controls), state)

    def n_cond(c):
        term, _, k = c
        return (k < spec.solve_iters) & (_ct_l1(term) > spec.solve_tol)

    def n_body(c):
        term, acc, k = c
        (term,) = vjp_state(term)
        return term, _add_cotangents(acc, term), k + 1

    _, u, _ = jax.lax.while_loop(
        n_cond, n_body, (w, w, jnp.zeros((), jnp.int32)))

    # pull u back through the map's dependence on inputs and controls.
    _, vjp_inputs = jax.vjp(lambda x, c: spec.step(state, x, c),
                            inputs, controls)
    dx_imp, dc_imp = vjp_inputs(u)
    return (_add_cotangents(dv_x, dx_imp), _add_cotangents(dv_c, dc_imp))


fixed_point_value.defvjp(_fpv_fwd, _fpv_bwd)
