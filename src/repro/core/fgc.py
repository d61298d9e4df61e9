"""Fast Gradient Computation (FGC) primitives — the paper's §3.

Everything reduces to applying, along one tensor axis of length N,

    (L x)_i  = Σ_{j<i} (i-j)^p x_j          L strictly-lower Toeplitz
    (Lᵀ x)_i = Σ_{j>i} (j-i)^p x_j          = flip(L(flip(x)))
    (D̃ x)   = L x + Lᵀ x                    D̃[i,j] = |i-j|^p  (0 diag for p≥1)

in O(p²·N) element-wise work instead of the dense O(N²) matvec.

Backends
--------
``scan``    paper-faithful DP recursion (eq. 3.9): the (p+1)-vector state
            a_{i+1} = P a_i + x_i·1 with P the Pascal lower-triangular matrix,
            run as a single `lax.scan` along the grid axis, vectorized over
            every other axis (TPU: state rides the VPU lanes).
``cumsum``  beyond-paper closed form: binomial expansion
            (i-j)^p = Σ_s C(p,s) i^{p-s} (-j)^s  turns Lx into p+1 exclusive
            cumulative sums — log-depth parallel prefix, no sequential loop.
            Indices are centered (i → i−N/2) to halve monomial magnitudes.
            A D̃-apply along an axis that fits one MXU tile (N ≤ 128), to
            more than one column, is instead one float32 matmul by the
            constant D̃ at HIGHEST precision: the paper's recursion blocked
            at one block, so no moment carry and no prefix sum.
``dense``   explicit Toeplitz matmul (oracle; MXU path for small N).
``pallas``  Pallas TPU kernel (see repro.kernels.fgc_scan), validated in
            interpret mode on CPU.

Fused D̃-apply
-------------
``apply_abs_power`` (the solvers' hot path — every gradient is built from
D̃-applies) no longer runs the historical two-pass form
``apply_L(x) + flip(apply_L(flip(x)))``.  Each backend has a fused
single-sweep implementation:

* ``scan``    ONE bidirectional `lax.scan` carrying both the L state and the
              Lᵀ state (two (p+1)-vectors); step i consumes x_i and x_{N−1−i}
              and emits both triangle contributions — N steps total instead
              of 2N across two scans.
* ``cumsum``  the p+1 moment cumsums Σ_j t_j^s x_j are computed ONCE and
              reused for both triangles (prefix reads for L, suffix =
              total − prefix for Lᵀ) — half the cumsum traffic of the
              two-pass form; an axis of N ≤ 128 is one (N, N) matmul.
* ``pallas``  fused TPU kernel (`fgc_scan.fgc_apply_dtilde_pallas`): one
              sequential row-block sweep computes block r of Lx and block
              nrb−1−r of Lᵀx per step, sharing the x block loads' DMA slots.
* ``blocked``/``dense`` keep their structure (dense is the oracle).

Batched solving over many (μ, ν) problems at once lives in
`repro.core.gw.entropic_gw_batch` / `repro.serve.engine.GWEngine`.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

BACKENDS = ("scan", "cumsum", "blocked", "dense", "pallas")


def default_float(dtype=None):
    """Context-derived float dtype: honors the x64 flag instead of silently
    downcasting a hard-wired float64 request (see kernels/ops.py)."""
    return jnp.result_type(float) if dtype is None else dtype


def pascal_matrix(p: int, dtype=jnp.float32):
    """(p+1)×(p+1) lower-triangular binomial matrix P[r,s] = C(r,s)."""
    m = [[math.comb(r, s) if s <= r else 0 for s in range(p + 1)]
         for r in range(p + 1)]
    return jnp.array(m, dtype=dtype)


def lower_toeplitz(n: int, p: int, dtype=None):
    """Dense L with L[i,j] = (i-j)^p for i>j, else 0 (dtype=None: derived
    via default_float)."""
    dtype = default_float(dtype)
    idx = jnp.arange(n, dtype=dtype)
    diff = idx[:, None] - idx[None, :]
    return jnp.where(diff > 0, diff ** p, jnp.zeros((), dtype))


def _dtilde_matrix(n: int, p: int, dtype):
    """Dense D̃ = L + Lᵀ, D̃[i,j] = |i-j|^p (p ≥ 1)."""
    lo = lower_toeplitz(n, p, dtype)
    return lo + lo.T


# ---------------------------------------------------------------------------
# axis canonicalization: move target axis to the front, flatten the rest.
# ---------------------------------------------------------------------------

def _to_front(x, axis):
    axis = axis % x.ndim
    x2 = jnp.moveaxis(x, axis, 0)
    lead = x2.shape[0]
    return x2.reshape(lead, -1), x2.shape, axis


def _from_front(y, shape, axis):
    return jnp.moveaxis(y.reshape(shape), 0, axis)


# ---------------------------------------------------------------------------
# L-apply backends (operate on (N, B) arrays along axis 0)
# ---------------------------------------------------------------------------

def _apply_L_scan(x2, p: int):
    """Paper eq. (3.9): a_{i+1} = P a_i + x_i·1,   y_i = a_i[p]."""
    n, b = x2.shape
    pasc = pascal_matrix(p, x2.dtype)

    def step(a, x_i):
        y_i = a[p]
        a_next = pasc @ a + x_i[None, :]
        return a_next, y_i

    a0 = jnp.zeros((p + 1, b), x2.dtype)
    _, ys = jax.lax.scan(step, a0, x2)
    return ys


def _apply_L_cumsum(x2, p: int):
    """Binomial-expanded closed form via p+1 exclusive cumsums."""
    n, b = x2.shape
    # centered indices keep monomials small: (i-j)^p is shift-invariant.
    t = (jnp.arange(n, dtype=x2.dtype) - jnp.asarray(n // 2, x2.dtype))
    y = jnp.zeros_like(x2)
    for s in range(p + 1):
        c = math.comb(p, s) * ((-1.0) ** s)
        ms = (t ** s)[:, None] * x2                       # j^s x_j
        cs = jnp.cumsum(ms, axis=0)
        excl = jnp.concatenate([jnp.zeros((1, b), x2.dtype), cs[:-1]], axis=0)
        y = y + c * (t ** (p - s))[:, None] * excl
    return y


def _apply_L_dense(x2, p: int):
    return lower_toeplitz(x2.shape[0], p, x2.dtype) @ x2


def _apply_L_blocked(x2, p: int, block: int = 16):
    """Blocked DP, GEMM-parallel form (beyond-paper; DESIGN.md §2).

    Split rows into R-blocks. The paper's recursion only needs to cross
    block boundaries through the (p+1) moment summaries, so the whole apply
    factors into THREE batched matmuls + one tiny scan:

        intra   = L_R · x_blk                 (batched GEMM, all blocks)
        moments = T · x_blk                   (batched GEMM)
        a_blk   = P_R · a_{blk−1} + moments   (scan of N/R steps on (p+1,B))
        y       = intra + V · a_blk           (batched GEMM)

    Sequential depth is N/R steps of O(p²·B) work; everything heavy is
    MXU/BLAS-shaped. Arithmetic O(N·R·B) with R ≪ N — the knob trading
    redundant intra-block work against sequential depth.
    """
    n, b = x2.shape
    r = min(block, n)
    pad = -n % r
    xp = jnp.pad(x2, ((0, pad), (0, 0)))
    nb = xp.shape[0] // r
    dtype = x2.dtype
    i = jnp.arange(r, dtype=dtype)
    diff = i[:, None] - i[None, :]
    l_r = jnp.where(diff > 0, diff ** p, jnp.zeros((), dtype))
    v = jnp.stack([math.comb(p, s) * i ** (p - s) for s in range(p + 1)], 1)
    p_r = jnp.array([[math.comb(rr, s) * float(r) ** (rr - s) if s <= rr
                      else 0.0 for s in range(p + 1)]
                     for rr in range(p + 1)], dtype)
    t = jnp.stack([(r - i) ** rr for rr in range(p + 1)], 0)

    xb = xp.reshape(nb, r, b)
    intra = jnp.einsum("rs,nsb->nrb", l_r, xb)
    moments = jnp.einsum("ps,nsb->npb", t, xb)

    def step(a, mom):
        return p_r @ a + mom, a          # emit the state at block START

    _, a_pref = jax.lax.scan(step, jnp.zeros((p + 1, b), dtype), moments)
    y = intra + jnp.einsum("rp,npb->nrb", v, a_pref)
    return y.reshape(nb * r, b)[:n]


def _apply_L_pallas(x2, p: int):
    from repro.kernels import ops as kops
    return kops.fgc_apply_l(x2, p)


_L_BACKENDS = {
    "scan": _apply_L_scan,
    "cumsum": _apply_L_cumsum,
    "blocked": _apply_L_blocked,
    "dense": _apply_L_dense,
    "pallas": _apply_L_pallas,
}


# ---------------------------------------------------------------------------
# fused D̃-apply backends: y = (L + Lᵀ) x in ONE sweep (no flip/L/flip pass)
# ---------------------------------------------------------------------------

def _apply_D_scan(x2, p: int):
    """Bidirectional DP: one `lax.scan` carries BOTH (p+1)-vector states.

    The forward stream (L recursion on x) and the reversed stream (L on
    flip(x), whose flipped output is Lᵀx) are concatenated along the batch
    axis, so step i is a single P @ a + x update on a (p+1, 2B) state — the
    two triangles ride the same vector lanes and D̃x is ONE n-step sweep
    instead of two.
    """
    n, b = x2.shape
    pasc = pascal_matrix(p, x2.dtype)
    xs = jnp.concatenate([x2, jnp.flip(x2, axis=0)], axis=1)

    def step(a, x_i):
        return pasc @ a + x_i[None, :], a[p]

    a0 = jnp.zeros((p + 1, 2 * b), x2.dtype)
    _, ys = jax.lax.scan(step, a0, xs)
    return ys[:, :b] + jnp.flip(ys[:, b:], axis=0)


# The MXU tile width of the TPU: an axis this short is one (N, N) tile.
TILE = 128


def _apply_D_cumsum(x2, p: int):
    """D̃x: one matmul by the constant D̃ on an axis of at most TILE points,
    the shared-moment cumsums on a longer one or on a single vector.

    HIGHEST keeps the float32 product float32 on the TPU, whose default f32
    dot is one bfloat16 pass.  A single vector costs little either way, and
    its matrix-vector product rounds differently from the matrix product
    that `jax.vmap` makes of it, so lanes of a batched solve would depend on
    the batch width.
    """
    n, b = x2.shape
    if n > TILE or b == 1:
        return _apply_D_moments(x2, p)
    with jax.ensure_compile_time_eval():
        d = _dtilde_matrix(n, p, x2.dtype)
    return jnp.dot(d, x2, precision=jax.lax.Precision.HIGHEST)


def _apply_D_moments(x2, p: int):
    """Shared-moment closed form: each cumsum Σ_j t_j^s x_j serves BOTH
    triangles — prefix (exclusive) for L, suffix = total − inclusive for Lᵀ —
    so D̃x costs p+1 cumsums instead of 2(p+1).

    L term s:  C(p,s)·(−1)^s     · t^{p−s} · Σ_{j<i} t_j^s x_j
    Lᵀ term s: C(p,s)·(−1)^{p−s} · t^{p−s} · Σ_{j>i} t_j^s x_j
    (the Lᵀ coefficient is the s′ = p−s term of (t_j − t_i)^p re-indexed so
    the j-exponent matches the shared moment).
    """
    n, b = x2.shape
    t = (jnp.arange(n, dtype=x2.dtype) - jnp.asarray(n // 2, x2.dtype))
    y = jnp.zeros_like(x2)
    for s in range(p + 1):
        ms = (t ** s)[:, None] * x2                      # t_j^s x_j
        cs = jnp.cumsum(ms, axis=0)
        excl_lo = jnp.concatenate([jnp.zeros((1, b), x2.dtype), cs[:-1]],
                                  axis=0)
        excl_hi = cs[-1][None, :] - cs
        w = math.comb(p, s) * (t ** (p - s))[:, None]
        y = y + w * (((-1.0) ** s) * excl_lo
                     + ((-1.0) ** (p - s)) * excl_hi)
    return y


def _apply_D_dense(x2, p: int):
    return _dtilde_matrix(x2.shape[0], p, x2.dtype) @ x2


def _apply_D_pallas(x2, p: int):
    from repro.kernels import ops as kops
    return kops.fgc_apply_dtilde(x2, p)


def _apply_D_two_pass(x2, p: int, backend: str):
    """Fallback for backends without a fused form (blocked)."""
    fn = _L_BACKENDS[backend]
    return fn(x2, p) + jnp.flip(fn(jnp.flip(x2, axis=0), p), axis=0)


_D_BACKENDS = {
    "scan": _apply_D_scan,
    "cumsum": _apply_D_cumsum,
    "blocked": partial(_apply_D_two_pass, backend="blocked"),
    "dense": _apply_D_dense,
    "pallas": _apply_D_pallas,
}


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def apply_L(x, axis: int = 0, power: int = 1, backend: str = "cumsum"):
    """y = L x along ``axis`` with L[i,j] = (i-j)^power, i>j."""
    if power < 0:
        raise ValueError("power must be >= 0")
    x2, shape, axis = _to_front(x, axis)
    y2 = _L_BACKENDS[backend](x2, power)
    return _from_front(y2, shape, axis)


def apply_LT(x, axis: int = 0, power: int = 1, backend: str = "cumsum"):
    """y = Lᵀ x along ``axis`` — reversal identity (paper §3)."""
    x2, shape, axis = _to_front(x, axis)
    y2 = _L_BACKENDS[backend](x2[::-1], power)[::-1]
    return _from_front(y2, shape, axis)


def apply_abs_power(x, axis: int = 0, power: int = 1, backend: str = "cumsum"):
    """y = D̃ x with D̃[i,j] = |i-j|^power (diagonal: 0^0 := 1 for power=0).

    power=0 is the all-ones matrix J (paper §3.1 Kronecker expansion term).
    Dispatches to the fused single-sweep backends (module docstring): D̃x is
    ONE pass over x, not an L-apply plus a flip/L/flip Lᵀ-apply.
    """
    if power < 0:
        raise ValueError("power must be >= 0")
    if power == 0:
        return jnp.sum(x, axis=axis, keepdims=True) * jnp.ones_like(x)
    x2, shape, axis = _to_front(x, axis)
    y2 = _D_BACKENDS[backend](x2, power)
    return _from_front(y2, shape, axis)


def flops_estimate(n: int, p: int) -> int:
    """Paper §3 cost: (N-1)·p(p+1)/2 muls + (N-1)(p+2)(p+1)/2 adds per L-apply."""
    return (n - 1) * (p * (p + 1) // 2 + (p + 2) * (p + 1) // 2)
