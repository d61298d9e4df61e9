"""Unit + property tests for the paper's core: FGC operators (§3)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _prop import given, settings, st

from repro.core import fgc

RNG = np.random.default_rng(0)
BACKENDS = ("scan", "cumsum", "pallas")


@pytest.mark.parametrize("n", [2, 5, 17, 64, 257])
@pytest.mark.parametrize("p", [0, 1, 2, 3])
@pytest.mark.parametrize("backend", BACKENDS)
def test_apply_abs_power_matches_dense(n, p, backend):
    x = jnp.asarray(RNG.normal(size=(n, 3)))
    want = fgc.apply_abs_power(x, 0, p, "dense")
    got = fgc.apply_abs_power(x, 0, p, backend)
    np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-9 * n ** p)


@pytest.mark.parametrize("backend", BACKENDS)
def test_apply_L_strictly_lower(backend):
    """(Lx)_0 must be 0 and (Lx)_i independent of x_j for j >= i."""
    n = 32
    x = jnp.asarray(RNG.normal(size=(n, 1)))
    y = fgc.apply_L(x, 0, 2, backend)
    assert float(jnp.abs(y[0]).max()) < 1e-12
    x2 = x.at[20:].set(123.0)
    y2 = fgc.apply_L(x2, 0, 2, backend)
    np.testing.assert_allclose(y[:21], y2[:21], rtol=1e-12)


@pytest.mark.parametrize("axis", [0, 1, 2])
def test_axis_handling(axis):
    x = jnp.asarray(RNG.normal(size=(6, 7, 8)))
    a = fgc.apply_abs_power(x, axis, 2, "cumsum")
    b = fgc.apply_abs_power(x, axis, 2, "dense")
    np.testing.assert_allclose(a, b, rtol=1e-9, atol=1e-9)


def test_LT_is_transpose_of_L():
    n = 40
    lo = np.asarray(fgc.lower_toeplitz(n, 2))
    x = jnp.asarray(RNG.normal(size=(n, 2)))
    got = fgc.apply_LT(x, 0, 2, "scan")
    np.testing.assert_allclose(got, lo.T @ np.asarray(x), rtol=1e-9,
                               atol=1e-9)


def test_pascal_matrix():
    p = np.asarray(fgc.pascal_matrix(3))
    want = np.array([[1, 0, 0, 0], [1, 1, 0, 0], [1, 2, 1, 0], [1, 3, 3, 1]])
    np.testing.assert_array_equal(p, want)


@settings(max_examples=25, deadline=None)
@given(n=st.integers(2, 40), p=st.integers(1, 3),
       seed=st.integers(0, 1000))
def test_property_backends_agree(n, p, seed):
    """The paper's DP recursion and the binomial-cumsum closed form are the
    same linear operator (hypothesis sweep)."""
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.normal(size=(n, 2)))
    a = fgc.apply_abs_power(x, 0, p, "scan")
    b = fgc.apply_abs_power(x, 0, p, "cumsum")
    np.testing.assert_allclose(a, b, rtol=1e-8, atol=1e-8 * n ** p)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 1000))
def test_property_linearity(seed):
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.normal(size=(20, 1)))
    y = jnp.asarray(r.normal(size=(20, 1)))
    a, b = 2.5, -1.25
    lhs = fgc.apply_abs_power(a * x + b * y, 0, 2, "scan")
    rhs = (a * fgc.apply_abs_power(x, 0, 2, "scan")
           + b * fgc.apply_abs_power(y, 0, 2, "scan"))
    np.testing.assert_allclose(lhs, rhs, rtol=1e-9, atol=1e-9)


@pytest.mark.parametrize("n", [2, 7, 16, 33, 64, 101])
@pytest.mark.parametrize("p", [0, 1, 2, 3, 4])
@pytest.mark.parametrize("backend", BACKENDS)
def test_fused_dtilde_matches_dense_oracle(n, p, backend):
    """Fused single-sweep D̃ backends vs the explicit lo + lo.T oracle,
    p ∈ {0..4}, odd and even N (f64)."""
    x = jnp.asarray(RNG.normal(size=(n, 2)))
    if p == 0:
        want = np.ones((n, n)) @ np.asarray(x)     # 0^0 := 1 on the diagonal
    else:
        lo = np.asarray(fgc.lower_toeplitz(n, p))
        want = (lo + lo.T) @ np.asarray(x)
    got = np.asarray(fgc.apply_abs_power(x, 0, p, backend))
    np.testing.assert_allclose(got, want, rtol=1e-9,
                               atol=1e-9 * max(1.0, float(n) ** p))


@pytest.mark.parametrize("p", [0, 1, 2, 3, 4])
@pytest.mark.parametrize("backend", BACKENDS)
def test_fused_dtilde_f32(p, backend):
    """Acceptance tolerance: fused D̃ within 1e-5 rtol of dense in f32."""
    x = jnp.asarray(RNG.normal(size=(200, 4)), dtype=jnp.float32)
    want = np.asarray(fgc.apply_abs_power(x, 0, p, "dense"))
    got = np.asarray(fgc.apply_abs_power(x, 0, p, backend))
    assert got.dtype == np.float32
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-5 * np.abs(want).max())


def _rel_err(got, want):
    return float(np.abs(np.asarray(got, np.float64) - want).max()
                 / np.abs(want).max())


@pytest.mark.parametrize("layout", ["1d", "2d", "3d"])
@pytest.mark.parametrize("p", [1, 2, 3, 4])
@pytest.mark.parametrize("n", [2, 33, 127, 128])
def test_cumsum_tile_dtilde_f32(n, p, layout):
    """An axis of at most one MXU tile takes the float32 matmul, a single
    vector the moment sums: both within 1e-5 of the float64 oracle, which
    one bfloat16 pass (the TPU's default f32 dot) misses."""
    shape, axis = {"1d": ((n,), 0), "2d": ((n, 3), 0),
                   "3d": ((3, n, 5), 1)}[layout]
    x = jnp.asarray(RNG.normal(size=shape), dtype=jnp.float32)
    lo = np.asarray(fgc.lower_toeplitz(n, p, jnp.float64))
    d = lo + lo.T
    want = np.moveaxis(np.tensordot(d, np.asarray(x, np.float64),
                                    axes=([1], [axis])), 0, axis)
    apply = lambda v: fgc.apply_abs_power(v, axis, p, "cumsum")  # noqa: E731
    got = apply(x)
    assert got.dtype == jnp.float32
    assert _rel_err(got, want) < 1e-5
    jaxpr = str(jax.make_jaxpr(apply)(x))
    assert ("dot_general" in jaxpr) == (layout != "1d")
    assert ("cumsum" in jaxpr) == (layout == "1d")
    if layout == "3d":    # the served form: vmapped lanes under jit
        lanes = jax.jit(jax.vmap(
            lambda v: fgc.apply_abs_power(v, 0, p, "cumsum")))(x)
        assert _rel_err(lanes, want) < 1e-5
    one_pass = jnp.moveaxis(jnp.tensordot(
        jnp.asarray(d, jnp.bfloat16), x.astype(jnp.bfloat16),
        axes=([1], [axis]), preferred_element_type=jnp.float32), 0, axis)
    assert _rel_err(one_pass, want) > 1e-5


@pytest.mark.parametrize("layout", ["2d", "3d"])
@pytest.mark.parametrize("p", [1, 2, 3, 4])
@pytest.mark.parametrize("n", [129, 200])
def test_cumsum_long_axis_keeps_moment_sums(n, p, layout):
    """Past one tile the cumsum backend is the p+1 shared-moment cumsums,
    bit for bit."""
    shape, axis = {"2d": ((n, 3), 0), "3d": ((3, n, 5), 1)}[layout]
    x = jnp.asarray(RNG.normal(size=shape), dtype=jnp.float32)
    x2, front, ax = fgc._to_front(x, axis)
    want = fgc._from_front(fgc._apply_D_moments(x2, p), front, ax)
    got = fgc.apply_abs_power(x, axis, p, "cumsum")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    jaxpr = jax.make_jaxpr(lambda v: fgc.apply_abs_power(v, axis, p,
                                                         "cumsum"))(x)
    assert "cumsum" in str(jaxpr) and "dot_general" not in str(jaxpr)


def test_fused_scan_is_single_sweep():
    """The fused scan backend must lower to exactly ONE lax.scan (the
    bidirectional sweep), not the historical L-pass + flip/L/flip pass."""
    x = jnp.asarray(RNG.normal(size=(33, 2)))
    jaxpr = jax.make_jaxpr(lambda v: fgc.apply_abs_power(v, 0, 2, "scan"))(x)
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1, jaxpr


def test_fused_matches_two_pass():
    """Fused D̃ must equal the explicit L + Lᵀ composition per backend."""
    x = jnp.asarray(RNG.normal(size=(47, 3)))
    for p in (1, 2, 3):
        for backend in ("scan", "cumsum"):
            fused = fgc.apply_abs_power(x, 0, p, backend)
            two = (fgc.apply_L(x, 0, p, backend)
                   + fgc.apply_LT(x, 0, p, backend))
            np.testing.assert_allclose(np.asarray(fused), np.asarray(two),
                                       rtol=1e-9, atol=1e-9 * 47.0 ** p)


def test_flops_estimate_matches_paper():
    # paper §3: (N−1)·k(k+1)/2 muls + (N−1)(k+2)(k+1)/2 adds
    assert fgc.flops_estimate(100, 1) == 99 * (1 + 3)
    assert fgc.flops_estimate(100, 2) == 99 * (3 + 6)
