"""Pallas TPU kernels: the fused factored-plan (low-rank coupling) inner loop.

Two hot paths of the `plan="lowrank"` solver (Scetbon et al. 2021 low-rank
Sinkhorn / PR 6's log-domain Dykstra projection) stream the (N, r) factor
blocks through VMEM in a single pass each:

1. `lr_dykstra_half_pallas` — ONE Dykstra sweep touches each factor-side
   kernel lk (an (N, r) log-array) exactly twice in XLA: a row logsumexp for
   the new row duals f and a column logsumexp (at the NEW f) for the coupled
   column-marginal block.  The fused kernel computes both in ONE streaming
   pass per factor side: per (BM, r) block it takes the row-LSE, forms the
   f block, folds the same block into an online per-column (max, sumexp)
   accumulator, and writes the finished column LSE on the last block.  The
   (r,)-sized dual/geometric-mean updates and the residual stay in XLA (they
   are O(r) and run once per sweep/chunk — the PR 5 "plan assembly stays in
   XLA" convention).

2. `lr_gram_chain_pallas` / `lr_grad_combine_pallas` — the factor-side Gram
   chain of `LowRankGradientOperator`.  The XLA path materializes
   U = D_X Q (M, r) between matmuls and reads Q three more times (column
   sums, tQ = Qᵀdx2, the quad-term apply).  The gram-chain kernel streams
   (A, B, Q, dx2) row blocks once over a two-phase sequential grid:
   phase 0 accumulates BᵀQ, the column sums, and Qᵀdx2 in VMEM scratch;
   phase 1 re-streams A·(BᵀQ) against Q into the (r, r) Gram — no (M, r)
   intermediate ever round-trips HBM.  The combine kernel then fuses the
   gradient assembly  (2(dx2 sᵀ + 1 tᵀ) − 4·A W)·diag(iq)  into one output
   pass.  The only reassociation vs XLA is Bᵀ(Q diag(iq))·B_gram =
   (BᵀQ)diag(iq)·B_gram — exact in ℝ, a few ulps in floating point, within
   the backend-parity contract below.

Every value operand (the log-kernels, duals, masses, Gram pieces — and
through them ε, γ', tol, `SolveControls` retunes) is TRACED; the only
static arguments are shapes and `interpret`.  One compiled executable
serves every ε-annealing stage and every retune — the PR 5 no-recompile
contract.  (ε/γ enter the Dykstra kernel pre-folded into lk by
`lr_mirror_step`, so they ride the same traced path as an SMEM scalar
would without re-doing the fold every sweep.)

Parity vs the XLA expressions is ≤1 ulp per sweep, not bitwise, for the
same reasons as `sinkhorn_step`: the 128-padded lane sums and the online
cross-block column renormalization associate reductions differently than
XLA's unpadded tree.  Zero-mass atoms (−inf log-mass / −inf kernel rows)
flow through exactly: a −inf row yields f = −inf (not NaN) via the same
guarded online-LSE used by the Sinkhorn kernels, and −inf-padded rank lanes
contribute exact zeros to every row sum.

vmap-compatibility: `pl.pallas_call`'s batching rule prepends the mapped
axis as an outermost grid dimension, so `entropic_gw_batch` lanes and
`GWEngine` buckets run these kernels grid-extended per-lane; the
`*_batched` wrappers expose that form eagerly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import scopes
from repro.kernels.sinkhorn_step import (BM, _cast_cost, _col, _finish_lse,
                                         _online_lse_update, _row,
                                         default_interpret)

#: rank/cost lane tile — factor ranks are small (8..64), one 128-lane tile
#: covers them; −inf (Dykstra) / zero (Gram) padding keeps the tail exact.
BR = 128


def _pad_axis(x, axis: int, mult: int, value):
    pad = [-s % mult if i == axis else 0 for i, s in enumerate(x.shape)]
    if not any(pad):
        return x
    return jnp.pad(x, [(0, p) for p in pad], constant_values=value)


# ---------------------------------------------------------------------------
# fused Dykstra half-sweep: row duals + online column LSE in one pass
# ---------------------------------------------------------------------------

def _dykstra_half_kernel(lk_ref, gcol_ref, logw_ref, f_ref, col_ref,
                         m_ref, s_ref, *, n_row_blocks: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        s_ref[...] = jnp.zeros_like(s_ref)

    # astype upcasts bf16 kernel tiles (cost_dtype="bf16"); no-op otherwise
    lk = lk_ref[...].astype(gcol_ref.dtype)                # (BM, RP)
    z = gcol_ref[...] + lk
    # row-LSE over the rank lanes (−inf-padded): matches jax.scipy's
    # logsumexp — amax + log Σ exp(z − amax), all-(−inf) rows pinned to −inf
    m1 = jnp.max(z, axis=1, keepdims=True)                 # (BM, 1)
    e = jnp.where(jnp.isfinite(m1), jnp.exp(z - m1), 0.0)
    lse1 = jnp.where(jnp.isfinite(m1),
                     m1 + jnp.log(jnp.sum(e, axis=1, keepdims=True)),
                     -jnp.inf)
    logw = logw_ref[...]
    f = jnp.where(logw > -jnp.inf, logw - lse1, -jnp.inf)
    f_ref[...] = f
    # fold the SAME block into the column LSE at the NEW f — exactly the
    # value the XLA sweep computes from (f_new, lk) in its second pass
    _online_lse_update(f + lk, m_ref, s_ref, axis=0)

    @pl.when(i == n_row_blocks - 1)
    def _finish():
        col_ref[...] = _finish_lse(m_ref[...], s_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret", "cost_dtype"))
def lr_dykstra_half_pallas(lk, gcol, logw, interpret: bool | None = None,
                           cost_dtype: str = "f32"):
    """One factor side of a Dykstra sweep, fused:

        f   = log w − LSE_lanes(gcol ⊕ lk)        (−inf on zero-mass rows)
        col = LSE_rows(f ⊕ lk)                    (at the NEW f)

    for lk an (N, r) log-kernel, gcol the (r,) column duals, log w the row
    log-masses.  All operands traced; returns (f, col).

    ``cost_dtype="bf16"`` streams the dominant (N, r) log-kernel tiles in
    bfloat16 (duals, accumulators, and outputs stay full precision; ±inf
    pins survive the cast) — see `sinkhorn_step._cast_cost`.
    """
    n, r = lk.shape
    dtype = lk.dtype
    lkp = _pad_axis(_pad_axis(lk, 0, BM, -jnp.inf), 1, BR, -jnp.inf)
    lkp = _cast_cost(lkp, cost_dtype)
    gp = _row(_pad_axis(gcol, 0, BR, 0.0))
    logwp = _col(_pad_axis(logw, 0, BM, -jnp.inf))
    rp = lkp.shape[1]
    grid = (lkp.shape[0] // BM,)

    f, col = pl.pallas_call(
        functools.partial(_dykstra_half_kernel, n_row_blocks=grid[0]),
        out_shape=(jax.ShapeDtypeStruct((lkp.shape[0], 1), dtype),
                   jax.ShapeDtypeStruct((1, rp), dtype)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BM, rp), lambda i: (i, 0)),
            pl.BlockSpec((1, rp), lambda i: (0, 0)),
            pl.BlockSpec((BM, 1), lambda i: (i, 0)),
        ],
        out_specs=(pl.BlockSpec((BM, 1), lambda i: (i, 0)),
                   pl.BlockSpec((1, rp), lambda i: (0, 0))),
        scratch_shapes=[pltpu.VMEM((1, rp), dtype),
                        pltpu.VMEM((1, rp), dtype)],
        interpret=default_interpret() if interpret is None else interpret,
        name=scopes.LR_DYKSTRA_HALF_KERNEL,
    )(lkp, gp, logwp)
    return f[:n, 0], col[0, :r]


def lr_dykstra_half_pallas_batched(lk, gcol, logw,
                                   interpret: bool | None = None,
                                   cost_dtype: str = "f32"):
    """Fused half-sweep over (B, N, r) lanes in one grid-extended launch."""
    return jax.vmap(functools.partial(lr_dykstra_half_pallas,
                                      interpret=interpret,
                                      cost_dtype=cost_dtype))(lk, gcol, logw)


# ---------------------------------------------------------------------------
# fused factor-Gram chain: BᵀQ, Qᵀ(A·BᵀQ), column sums, Qᵀw in two phases
# ---------------------------------------------------------------------------

def _dot(x, y):
    return jax.lax.dot_general(x, y, (((x.ndim - 1,), (0,)), ((), ())),
                               preferred_element_type=x.dtype)


def _dot_t(x, y):
    """xᵀ y contracting the leading (row-block) axis — no explicit
    transpose of the VMEM tile."""
    return jax.lax.dot_general(x, y, (((0,), (0,)), ((), ())),
                               preferred_element_type=x.dtype)


def _gram_chain_kernel(a_ref, b_ref, q_ref, w_ref,
                       bq_out, gram_out, sq_out, tq_out,
                       bq_acc, gram_acc, sq_acc, tq_acc, *,
                       n_row_blocks: int):
    phase = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when((phase == 0) & (i == 0))
    def _init():
        bq_acc[...] = jnp.zeros_like(bq_acc)
        gram_acc[...] = jnp.zeros_like(gram_acc)
        sq_acc[...] = jnp.zeros_like(sq_acc)
        tq_acc[...] = jnp.zeros_like(tq_acc)

    q = q_ref[...]                                         # (BM, RP)

    @pl.when(phase == 0)
    def _accumulate_first_pass():
        bq_acc[...] += _dot_t(b_ref[...], q)               # BᵀQ   (CP, RP)
        sq_acc[...] += jnp.sum(q, axis=0)[None, :]
        tq_acc[...] += _dot_t(w_ref[...], q)               # wᵀQ   (1, RP)

    @pl.when(phase == 1)
    def _accumulate_gram():
        u = _dot(a_ref[...], bq_acc[...])                  # A(BᵀQ) (BM, RP)
        gram_acc[...] += _dot_t(q, u)                      # QᵀU    (RP, RP)

    @pl.when((phase == 1) & (i == n_row_blocks - 1))
    def _finish():
        bq_out[...] = bq_acc[...]
        gram_out[...] = gram_acc[...]
        sq_out[...] = sq_acc[...]
        tq_out[...] = tq_acc[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def lr_gram_chain_pallas(a_fac, b_fac, q, w, interpret: bool | None = None):
    """Fused factor-side Gram chain for D = A_fac·B_facᵀ and factor Q:

        bq = B_facᵀ Q   (c, r)     gram = Qᵀ(A_fac bq) = Qᵀ D Q   (r, r)
        sq = Qᵀ 1       (r,)       tq   = Qᵀ w                    (r,)

    in ONE two-phase streaming pass (phase 0: bq/sq/tq accumulate; phase 1:
    the Gram re-streams A against the finished bq) — the (N, r) intermediate
    D Q of the XLA chain never exists in HBM.  Zero row/lane padding is
    exact for every product.  Returns (bq, gram, sq, tq).
    """
    n, c = a_fac.shape
    r = q.shape[1]
    dtype = q.dtype
    ap = _pad_axis(_pad_axis(a_fac, 0, BM, 0.0), 1, BR, 0.0)
    bp = _pad_axis(_pad_axis(b_fac, 0, BM, 0.0), 1, BR, 0.0)
    qp = _pad_axis(_pad_axis(q, 0, BM, 0.0), 1, BR, 0.0)
    wp = _col(_pad_axis(w, 0, BM, 0.0))
    cp, rp = ap.shape[1], qp.shape[1]
    nb = ap.shape[0] // BM
    grid = (2, nb)

    bq, gram, sq, tq = pl.pallas_call(
        functools.partial(_gram_chain_kernel, n_row_blocks=nb),
        out_shape=(jax.ShapeDtypeStruct((cp, rp), dtype),
                   jax.ShapeDtypeStruct((rp, rp), dtype),
                   jax.ShapeDtypeStruct((1, rp), dtype),
                   jax.ShapeDtypeStruct((1, rp), dtype)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BM, cp), lambda p, i: (i, 0)),
            pl.BlockSpec((BM, cp), lambda p, i: (i, 0)),
            pl.BlockSpec((BM, rp), lambda p, i: (i, 0)),
            pl.BlockSpec((BM, 1), lambda p, i: (i, 0)),
        ],
        out_specs=(pl.BlockSpec((cp, rp), lambda p, i: (0, 0)),
                   pl.BlockSpec((rp, rp), lambda p, i: (0, 0)),
                   pl.BlockSpec((1, rp), lambda p, i: (0, 0)),
                   pl.BlockSpec((1, rp), lambda p, i: (0, 0))),
        scratch_shapes=[pltpu.VMEM((cp, rp), dtype),
                        pltpu.VMEM((rp, rp), dtype),
                        pltpu.VMEM((1, rp), dtype),
                        pltpu.VMEM((1, rp), dtype)],
        interpret=default_interpret() if interpret is None else interpret,
        name=scopes.LR_GRAM_CHAIN_KERNEL,
    )(ap, bp, qp, wp)
    return bq[:c, :r], gram[:r, :r], sq[0, :r], tq[0, :r]


def lr_gram_chain_pallas_batched(a_fac, b_fac, q, w,
                                 interpret: bool | None = None):
    """Gram chain over (B, N, ·) lanes in one grid-extended launch."""
    return jax.vmap(functools.partial(lr_gram_chain_pallas,
                                      interpret=interpret))(a_fac, b_fac, q,
                                                            w)


# ---------------------------------------------------------------------------
# fused gradient assembly: (2(d2 sᵀ + 1 tᵀ) − 4·A_fac W)·diag(iq), one pass
# ---------------------------------------------------------------------------

def _grad_combine_kernel(a_ref, d2_ref, w_ref, s_ref, t_ref, iq_ref,
                         out_ref):
    quad = _dot(a_ref[...], w_ref[...])                    # (BM, RP)
    out_ref[...] = (2.0 * (d2_ref[...] * s_ref[...] + t_ref[...])
                    - 4.0 * quad) * iq_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def lr_grad_combine_pallas(a_fac, w_small, d2, s_other, t_other, iq,
                           interpret: bool | None = None):
    """∇_Q assembly in one output pass:

        out = (2(d2 s_otherᵀ + 1 t_otherᵀ) − 4·A_fac W)·diag(iq)

    with W = (BᵀQ diag(iq))·Gram_other the (c, r) quad-term seed (computed
    by the caller from `lr_gram_chain_pallas` outputs — O(c·r²), no factor
    pass).  The dense (N, r) gradient is written exactly once; no (N, r)
    temporaries exist between the matmul and the elementwise tail.
    """
    n, c = a_fac.shape
    r = iq.shape[0]
    dtype = iq.dtype
    ap = _pad_axis(_pad_axis(a_fac, 0, BM, 0.0), 1, BR, 0.0)
    d2p = _col(_pad_axis(d2, 0, BM, 0.0))
    sp = _row(_pad_axis(s_other, 0, BR, 0.0))
    tp = _row(_pad_axis(t_other, 0, BR, 0.0))
    iqp = _row(_pad_axis(iq, 0, BR, 0.0))
    cp, rp = ap.shape[1], iqp.shape[1]
    # w_small rows live on the cost axis: pad to the a-block lane width
    wp = _pad_axis(_pad_axis(w_small, 0, cp, 0.0), 1, BR, 0.0)
    grid = (ap.shape[0] // BM,)

    out = pl.pallas_call(
        _grad_combine_kernel,
        out_shape=jax.ShapeDtypeStruct((ap.shape[0], rp), dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BM, cp), lambda i: (i, 0)),
            pl.BlockSpec((BM, 1), lambda i: (i, 0)),
            pl.BlockSpec((cp, rp), lambda i: (0, 0)),
            pl.BlockSpec((1, rp), lambda i: (0, 0)),
            pl.BlockSpec((1, rp), lambda i: (0, 0)),
            pl.BlockSpec((1, rp), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((BM, rp), lambda i: (i, 0)),
        interpret=default_interpret() if interpret is None else interpret,
        name=scopes.LR_GRAD_COMBINE_KERNEL,
    )(ap, d2p, wp, sp, tp, iqp)
    return out[:n, :r]


def lr_grad_combine_pallas_batched(a_fac, w_small, d2, s_other, t_other, iq,
                                   interpret: bool | None = None):
    """Gradient assembly over (B, N, ·) lanes in one grid-extended launch."""
    return jax.vmap(functools.partial(lr_grad_combine_pallas,
                                      interpret=interpret))(
        a_fac, w_small, d2, s_other, t_other, iq)
