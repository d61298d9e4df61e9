"""Pallas TPU kernel for the FGC L-apply (paper eq. 3.9), blocked for the MXU.

Hardware adaptation (DESIGN.md §2): the paper's recursion is a scalar DP —
one multiply-add chain per grid point — which would serialize the VPU.  We
re-block it: process R=128 rows at a time, carrying the paper's (k+1)-moment
state a_start[s] = Σ_{j<start} (start−j)^s x_j across blocks.  Within a block,

    y_block   = L_R · x_block  +  V · a_start            (MXU matmuls)
    a_end     = P_R · a_start  +  T · x_block

where (all precomputed at trace time for static k, R):
    L_R[i,j]  = (i−j)^k, i>j           (R×R strictly-lower Toeplitz)
    V[i,s]    = C(k,s) · i^{k−s}       (R×(k+1): extrapolates old state)
    P_R[r,s]  = C(r,s) · R^{r−s}       ((k+1)²: shifts state by R)
    T[r,j]    = (R−j)^r                ((k+1)×R: absorbs the new block)

Sequential steps drop from N to N/R; each step is matmul work the MXU eats.
Grid: (column-blocks × row-blocks), row dim innermost/sequential, state in a
VMEM scratch that persists across the row sweep.  VMEM per program:
(R+1+2(k+1))×128 f32 ≈ 130 KB at R=128 — comfortably inside 16 MB, so R can
be raised to amortize further (see §Perf).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import scopes

LANES = 128
BLOCK_ROWS = 128


def _block_constants(p: int, r: int, dtype):
    i = jnp.arange(r, dtype=dtype)
    diff = i[:, None] - i[None, :]
    l_r = jnp.where(diff > 0, diff ** p, jnp.zeros((), dtype))
    v = jnp.stack([math.comb(p, s) * i ** (p - s) for s in range(p + 1)],
                  axis=1)
    p_r = jnp.array([[math.comb(rr, s) * float(r) ** (rr - s) if s <= rr
                      else 0.0 for s in range(p + 1)]
                     for rr in range(p + 1)], dtype)
    t = jnp.stack([(r - i) ** rr for rr in range(p + 1)], axis=0)
    return l_r.astype(dtype), v.astype(dtype), p_r, t.astype(dtype)


def _fgc_kernel(x_ref, l_ref, v_ref, pr_ref, t_ref, y_ref, acc_ref, *,
                p: int, block_rows: int):
    dtype = x_ref.dtype
    row_idx = pl.program_id(1)

    @pl.when(row_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    a = acc_ref[...]
    y = (jnp.dot(l_ref[...], x, preferred_element_type=dtype)
         + jnp.dot(v_ref[...], a, preferred_element_type=dtype))
    acc_ref[...] = (jnp.dot(pr_ref[...], a, preferred_element_type=dtype)
                    + jnp.dot(t_ref[...], x, preferred_element_type=dtype))
    y_ref[...] = y


def _dtilde_kernel(x_ref, xm_ref, l_ref, v_ref, pr_ref, t_ref, lt_ref,
                   vr_ref, tr_ref, ylo_ref, yhi_ref, a_ref, b_ref, *, p: int,
                   block_rows: int):
    """Fused D̃ = L + Lᵀ step: ONE sequential row-block sweep.

    At row step r the kernel sees block r of x (forward stream) and block
    nrb−1−r (mirror stream).  The forward stream runs the L recursion into
    output block r.  The mirror stream, row-reversed (J xm, J the R×R
    anti-identity), is block r of the reversed sequence x̃; the SAME L
    recursion on it, row-reversed, is output block nrb−1−r of Lᵀx
    (Lᵀx = flip(L x̃)).  Both reversals are folded into constants
    (J L J = L_Rᵀ, J V, T J), because the TPU lowering has no in-kernel
    row reversal:

        yhi = L_Rᵀ xm + (J V) b        b' = P_R b + (T J) xm

    Two (p+1)-moment states live in VMEM scratch; the final D̃x is the sum
    of the two outputs (done outside the kernel).
    """
    dtype = x_ref.dtype
    row_idx = pl.program_id(1)

    @pl.when(row_idx == 0)
    def _init():
        a_ref[...] = jnp.zeros_like(a_ref)
        b_ref[...] = jnp.zeros_like(b_ref)

    def dot(u, w):
        return jnp.dot(u, w, preferred_element_type=dtype)

    x = x_ref[...]
    xm = xm_ref[...]
    a = a_ref[...]
    b = b_ref[...]
    ylo_ref[...] = dot(l_ref[...], x) + dot(v_ref[...], a)
    yhi_ref[...] = dot(lt_ref[...], xm) + dot(vr_ref[...], b)
    a_ref[...] = dot(pr_ref[...], a) + dot(t_ref[...], x)
    b_ref[...] = dot(pr_ref[...], b) + dot(tr_ref[...], xm)


@functools.partial(jax.jit,
                   static_argnames=("p", "block_rows", "interpret"))
def fgc_apply_dtilde_pallas(x, p: int = 1, block_rows: int = BLOCK_ROWS,
                            interpret: bool = True):
    """y = D̃ x = (L + Lᵀ) x along axis 0 of (N, B) x, fused single sweep.

    Same padding rules as the L-apply: trailing zero rows are inert for both
    triangles (strictly-lower L never reads forward; for Lᵀ the padded rows
    carry zero mass), so the [:n] slice is exact.
    """
    n, b = x.shape
    dtype = x.dtype
    xp = jnp.pad(x, ((0, -n % block_rows), (0, -b % LANES)))
    np_, bp_ = xp.shape
    nrb = np_ // block_rows
    grid = (bp_ // LANES, nrb)  # rows innermost => sequential
    l_r, v, p_r, t = _block_constants(p, block_rows, dtype)
    consts = (l_r, v, p_r, t, l_r.T, v[::-1], t[:, ::-1])

    def _const_spec(arr):
        return pl.BlockSpec(arr.shape, lambda c, r: (0,) * arr.ndim)

    y_lo, y_hi = pl.pallas_call(
        functools.partial(_dtilde_kernel, p=p, block_rows=block_rows),
        out_shape=[jax.ShapeDtypeStruct(xp.shape, dtype),
                   jax.ShapeDtypeStruct(xp.shape, dtype)],
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, LANES), lambda c, r: (r, c)),
                  pl.BlockSpec((block_rows, LANES),
                               lambda c, r: (nrb - 1 - r, c)),
                  *(_const_spec(k) for k in consts)],
        out_specs=[pl.BlockSpec((block_rows, LANES), lambda c, r: (r, c)),
                   pl.BlockSpec((block_rows, LANES),
                                lambda c, r: (nrb - 1 - r, c))],
        scratch_shapes=[pltpu.VMEM((p + 1, LANES), dtype),
                        pltpu.VMEM((p + 1, LANES), dtype)],
        interpret=interpret,
        name=scopes.FGC_DTILDE_KERNEL,
    )(xp, xp, *consts)
    return (y_lo + y_hi)[:n, :b]


@functools.partial(jax.jit,
                   static_argnames=("p", "block_rows", "interpret"))
def fgc_apply_l_pallas(x, p: int = 1, block_rows: int = BLOCK_ROWS,
                       interpret: bool = True):
    """y = L x along axis 0 of (N, B) x, with L[i,j] = (i−j)^p (i>j).

    Pads N up to a multiple of ``block_rows`` (trailing zero rows cannot
    influence earlier outputs — L is strictly lower) and B up to 128 lanes.
    """
    n, b = x.shape
    dtype = x.dtype
    xp = jnp.pad(x, ((0, -n % block_rows), (0, -b % LANES)))
    np_, bp_ = xp.shape
    grid = (bp_ // LANES, np_ // block_rows)  # rows innermost => sequential
    l_r, v, p_r, t = _block_constants(p, block_rows, dtype)

    def _const_spec(arr):
        return pl.BlockSpec(arr.shape, lambda c, r: (0,) * arr.ndim)

    y = pl.pallas_call(
        functools.partial(_fgc_kernel, p=p, block_rows=block_rows),
        out_shape=jax.ShapeDtypeStruct(xp.shape, dtype),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, LANES), lambda c, r: (r, c)),
                  _const_spec(l_r), _const_spec(v), _const_spec(p_r),
                  _const_spec(t)],
        out_specs=pl.BlockSpec((block_rows, LANES), lambda c, r: (r, c)),
        scratch_shapes=[pltpu.VMEM((p + 1, LANES), dtype)],
        interpret=interpret,
        name=scopes.FGC_L_KERNEL,
    )(xp, l_r, v, p_r, t)
    return y[:n, :b]
