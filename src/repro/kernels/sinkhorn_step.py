"""Pallas TPU kernels: fused log-domain Sinkhorn half-steps (flash-style).

One mirror-descent inner iteration needs the row update
    f_i = ε·(log μ_i − logsumexp_p (g_p − C_ip)/ε)
and its column twin
    g_p = ε·(log ν_p − logsumexp_i (f_i − C_ip)/ε)
which, done naively, materialize (g − C)/ε and two more (M,N) temporaries
per half-step.  These kernels stream C through VMEM in (BM×BN) tiles with an
online (max, sumexp) reduction — one pass over C per half-step, no (M,N)
temporaries.  The column kernel walks the SAME row-major C with the row axis
innermost, so neither half-step ever materializes Cᵀ.

Grid: (parallel-blocks × reduction-blocks), reduction innermost/sequential;
running per-output max m and sum s live in VMEM scratch; the output is
written on the last reduction step.

ε is a TRACED scalar operand delivered through SMEM — ε-annealing (a new ε
every outer stage) and `SolveControls` retuning reuse one compiled
executable instead of recompiling per stage.  It is a (1, 1) block: under
vmap the operand becomes (B, 1, 1), whose last two block dims still equal
the array's, as the TPU lowering requires (a (1,) block would become a
(B, 1) array with a squeezed, non-full second-to-last dim).  The kernel
divides by ε exactly as the XLA path does (`(g − C)/ε`, not a reciprocal
multiply).
Parity vs `jax.scipy` logsumexp is ≤1 ulp per half-step, not bitwise: the
+inf-padded 128-wide tile sums (and, across tiles, the online
renormalization) associate the reduction differently than XLA's unpadded
tree — and the XLA expressions themselves round differently between eager
and scan-fused contexts.  What IS exact is every within-backend
invariance: chunked tol=0 == fixed scan, warm starts, segmented ==
one-shot, continuous serving == barrier (tests/test_sinkhorn_backend.py).

Zero-mass atoms (the `zero_mass_potentials` convention of
`repro.core.sinkhorn`: batch-padded support points carry −inf potentials
and −inf log-mass) flow through without NaN: a tile whose running max is
still −inf contributes 0 to the sum (`exp(−inf − (−inf))` would be NaN),
and an all-masked output row yields lse = −inf, matching
`logsumexp(all −inf) = −inf` exactly.

`interpret=None` auto-selects: compiled on TPU, interpreter elsewhere (the
CPU-container correctness path used by the test-suite parity pins).

vmap-compatibility: `pl.pallas_call` has a batching rule that prepends the
mapped axis as an outermost grid dimension, so these kernels work per-lane
under `entropic_gw_batch`'s vmap — including per-lane traced ε.  The
`*_batched` wrappers expose that grid-extended form eagerly for (B, M, N)
stacks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import scopes

BM = 128
BN = 128


def default_interpret() -> bool:
    """Interpret off-TPU (Pallas' CPU correctness path), compiled on TPU."""
    return jax.default_backend() != "tpu"


def _online_lse_update(z, m_ref, s_ref, axis: int):
    """One tile of the online (max, sumexp) reduction over ``axis``.

    The two `where` guards keep zero-mass regions exact: while every tile
    seen so far is fully masked (z = −inf everywhere, so the running max is
    −inf) both the rescale of the old sum and the new tile's contribution
    must be literally 0 — the unguarded forms are exp(−inf − (−inf)) = NaN,
    and one NaN would otherwise poison the running sum for good.  Once the
    max is finite the guards select the untouched fast path bit-for-bit.
    """
    m_old = m_ref[...]
    m_new = jnp.maximum(m_old, jnp.max(z, axis=axis, keepdims=True))
    scale = jnp.where(jnp.isfinite(m_old), jnp.exp(m_old - m_new), 0.0)
    contrib = jnp.where(jnp.isfinite(m_new), jnp.exp(z - m_new), 0.0)
    s_ref[...] = (s_ref[...] * scale
                  + jnp.sum(contrib, axis=axis, keepdims=True))
    m_ref[...] = m_new


def _finish_lse(m, s):
    """lse = m + log s, with all-masked outputs pinned to −inf (matching
    `logsumexp` of an all-−inf row) instead of −inf + log 0 = NaN."""
    return jnp.where(jnp.isfinite(m), m + jnp.log(s), -jnp.inf)


def _row_kernel(eps_ref, cost_ref, g_ref, logmu_ref, f_ref, m_ref, s_ref, *,
                n_col_blocks: int):
    col = pl.program_id(1)
    eps = eps_ref[0, 0]

    @pl.when(col == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        s_ref[...] = jnp.zeros_like(s_ref)

    # divide (not reciprocal-multiply) so interpret mode matches the XLA
    # path's (g − C)/ε rounding bit-for-bit; the astype upcasts bf16 cost
    # tiles (cost_dtype="bf16") and is a no-op at matching dtypes
    z = (g_ref[...]
         - cost_ref[...].astype(g_ref.dtype)) / eps        # (BM, BN)
    _online_lse_update(z, m_ref, s_ref, axis=1)

    @pl.when(col == n_col_blocks - 1)
    def _finish():
        lse = _finish_lse(m_ref[...], s_ref[...])           # (BM, 1)
        f_ref[...] = eps * (logmu_ref[...] - lse)


def _col_kernel(eps_ref, cost_ref, f_ref, lognu_ref, g_ref, m_ref, s_ref, *,
                n_row_blocks: int):
    row = pl.program_id(1)
    eps = eps_ref[0, 0]

    @pl.when(row == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        s_ref[...] = jnp.zeros_like(s_ref)

    z = (f_ref[...]
         - cost_ref[...].astype(f_ref.dtype)) / eps        # (BM, BN)
    _online_lse_update(z, m_ref, s_ref, axis=0)

    @pl.when(row == n_row_blocks - 1)
    def _finish():
        lse = _finish_lse(m_ref[...], s_ref[...])           # (1, BN)
        g_ref[...] = eps * (lognu_ref[...] - lse)


# Vector operands travel as 2-D blocks: Mosaic tiles a 1-D f32 block by 128
# while XLA tiles a 1-D f32 array by 1024, so (BM,) blocks are refused on the
# TPU.  Column-indexed vectors are lane-dense (1, N) rows, row-indexed ones
# (M, 1) columns — the orientations the kernels broadcast them in.
def _row(v):
    return v.reshape(1, -1)


def _col(v):
    return v.reshape(-1, 1)


def _pad_operands(cost, v, w, bm: int, bn: int):
    """Pad C to (⌈M/BM⌉·BM, ⌈N/BN⌉·BN) with +inf — exp((· − inf)/ε) = 0, so
    padded cells never contribute — and the vectors with zeros; the
    column-indexed ``v`` comes back as a (1, N) row, the row-indexed ``w``
    as an (M, 1) column."""
    m, n = cost.shape
    mp, np_ = -m % bm, -n % bn
    costp = jnp.pad(cost, ((0, mp), (0, np_)), constant_values=jnp.inf)
    return costp, _row(jnp.pad(v, (0, np_))), _col(jnp.pad(w, (0, mp)))


def _cast_cost(costp, cost_dtype: str):
    """The opt-in bandwidth knob: ``cost_dtype="bf16"`` streams the cost
    tiles as bfloat16 (half the HBM traffic of the dominant operand); the
    kernels upcast each tile before the f32 online reduction, so duals,
    scratch accumulators, and outputs keep full precision.  ±inf padding
    survives the cast (bf16 carries infinities)."""
    if cost_dtype == "f32":
        return costp
    if cost_dtype == "bf16":
        return costp.astype(jnp.bfloat16)
    raise ValueError(f"unknown cost_dtype {cost_dtype!r}: "
                     "expected 'f32' or 'bf16'")


@functools.partial(jax.jit, static_argnames=("interpret", "cost_dtype"))
def sinkhorn_row_update_pallas(cost, g, log_mu, eps,
                               interpret: bool | None = None,
                               cost_dtype: str = "f32"):
    """f = ε(log μ − LSE_p((g_p − C_ip)/ε)) for (M,N) cost; fused single
    pass.  ``eps`` is traced (SMEM scalar): annealing never recompiles.
    ``cost_dtype="bf16"`` streams C's tiles in bfloat16 (see `_cast_cost`)."""
    m, _ = cost.shape
    dtype = cost.dtype
    costp, gp, logmup = _pad_operands(cost, g, log_mu, BM, BN)
    costp = _cast_cost(costp, cost_dtype)
    grid = (costp.shape[0] // BM, costp.shape[1] // BN)
    eps_arr = jnp.asarray(eps, dtype).reshape((1, 1))

    f = pl.pallas_call(
        functools.partial(_row_kernel, n_col_blocks=grid[1]),
        out_shape=jax.ShapeDtypeStruct((costp.shape[0], 1), dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda r, c: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((BM, BN), lambda r, c: (r, c)),
            pl.BlockSpec((1, BN), lambda r, c: (0, c)),
            pl.BlockSpec((BM, 1), lambda r, c: (r, 0)),
        ],
        out_specs=pl.BlockSpec((BM, 1), lambda r, c: (r, 0)),
        scratch_shapes=[pltpu.VMEM((BM, 1), dtype),
                        pltpu.VMEM((BM, 1), dtype)],
        interpret=default_interpret() if interpret is None else interpret,
        name=scopes.SINKHORN_ROW_KERNEL,
    )(eps_arr, costp, gp, logmup)
    return f[:m, 0]


@functools.partial(jax.jit, static_argnames=("interpret", "cost_dtype"))
def sinkhorn_col_update_pallas(cost, f, log_nu, eps,
                               interpret: bool | None = None,
                               cost_dtype: str = "f32"):
    """g = ε(log ν − LSE_i((f_i − C_ip)/ε)): the Cᵀ twin as a true column
    kernel — the SAME row-major C tiles stream through VMEM with the row
    axis innermost, so no transposed copy of C is ever materialized."""
    _, n = cost.shape
    dtype = cost.dtype
    costp, lognup, fp = _pad_operands(cost, log_nu, f, BM, BN)
    costp = _cast_cost(costp, cost_dtype)
    grid = (costp.shape[1] // BN, costp.shape[0] // BM)
    eps_arr = jnp.asarray(eps, dtype).reshape((1, 1))

    g = pl.pallas_call(
        functools.partial(_col_kernel, n_row_blocks=grid[1]),
        out_shape=jax.ShapeDtypeStruct((1, costp.shape[1]), dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda c, r: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((BM, BN), lambda c, r: (r, c)),
            pl.BlockSpec((BM, 1), lambda c, r: (r, 0)),
            pl.BlockSpec((1, BN), lambda c, r: (0, c)),
        ],
        out_specs=pl.BlockSpec((1, BN), lambda c, r: (0, c)),
        scratch_shapes=[pltpu.VMEM((1, BN), dtype),
                        pltpu.VMEM((1, BN), dtype)],
        interpret=default_interpret() if interpret is None else interpret,
        name=scopes.SINKHORN_COL_KERNEL,
    )(eps_arr, costp, fp, lognup)
    return g[0, :n]


def _batched(fn, cost, v, w, eps, interpret, cost_dtype):
    eps = jnp.broadcast_to(jnp.asarray(eps, cost.dtype), cost.shape[:1])
    return jax.vmap(functools.partial(fn, interpret=interpret,
                                      cost_dtype=cost_dtype))(cost, v, w,
                                                              eps)


def sinkhorn_row_update_pallas_batched(cost, g, log_mu, eps,
                                       interpret: bool | None = None,
                                       cost_dtype: str = "f32"):
    """Row half-step over (B, M, N) lanes in ONE grid-extended launch —
    Pallas' vmap batching rule prepends the lane axis as the outermost grid
    dimension.  ``eps`` may be scalar (shared) or (B,) (per-lane, as the
    serving path's stacked `SolveControls` deliver it)."""
    return _batched(sinkhorn_row_update_pallas, cost, g, log_mu, eps,
                    interpret, cost_dtype)


def sinkhorn_col_update_pallas_batched(cost, f, log_nu, eps,
                                       interpret: bool | None = None,
                                       cost_dtype: str = "f32"):
    """Column half-step over (B, M, N) lanes; see the row twin."""
    return _batched(sinkhorn_col_update_pallas, cost, f, log_nu, eps,
                    interpret, cost_dtype)
