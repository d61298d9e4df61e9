"""The plain reference against the program at small sizes on the CPU: a
grid solve through ``entropic_gw`` and point-cloud requests through
``GWEngine.serve`` walk the same iterations to the same answers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import gw as ref

GRID_SOLVER = {"eps": 0.004, "eps_init": 0.05, "anneal_decay": 0.5,
               "outer_iters": 8, "sinkhorn_iters": 200, "sinkhorn_chunk": 25,
               "tol": 1e-4}
CLOUD_SOLVER = {"eps": 0.2, "eps_init": 1.0, "anneal_decay": 0.5,
                "outer_iters": 30, "sinkhorn_iters": 200,
                "sinkhorn_chunk": 25, "tol": 1e-4}


def _measure(key, n):
    u = jax.random.uniform(key, (n,), jnp.float32) + 1e-3
    return u / u.sum()


def test_grid_distance_is_the_manhattan_distance():
    d = np.asarray(ref.grid2d_distance(3, 1))
    h = 0.5
    assert d[0, 8] == pytest.approx(4 * h)      # (0,0) to (2,2)
    assert d[1, 3] == pytest.approx(2 * h)      # (0,1) to (1,0)
    np.testing.assert_array_equal(d, d.T)
    d2 = np.asarray(ref.grid2d_distance(3, 2))
    np.testing.assert_allclose(d2, d ** 2, rtol=1e-6)


def test_sqeuclidean_distance_matches_numpy():
    p = np.random.default_rng(0).normal(size=(7, 3)).astype(np.float32)
    want = ((p[:, None] - p[None]) ** 2).sum(-1)
    np.testing.assert_allclose(np.asarray(ref.sqeuclidean_distance(p)),
                               want, rtol=1e-6)


@pytest.mark.parametrize("side", [6, 8])
def test_reference_walks_entropic_gw_on_a_small_grid(side):
    from repro.core.grids import Grid2D
    from repro.core.gw import GWConfig, entropic_gw

    k = jax.random.PRNGKey(side)
    mu = _measure(jax.random.fold_in(k, 0), side * side)
    nu = _measure(jax.random.fold_in(k, 1), side * side)
    grid = Grid2D(side, 1.0 / (side - 1), 1)
    cfg = GWConfig(**GRID_SOLVER, backend="cumsum", sinkhorn_backend="xla")
    got = jax.jit(lambda a, b: entropic_gw(grid, grid, a, b, cfg))(mu, nu)
    d = ref.grid2d_distance(side, 1)
    want = ref.solve(d, d, mu, nu, ref.Settings.of(GRID_SOLVER))
    assert int(got.info.outer_iters) == want.outer_iters
    assert int(got.info.inner_iters) == want.inner_iters
    assert float(got.value) == pytest.approx(want.value, rel=1e-4)
    assert ref.l1(got.plan, want.plan) < 1e-4
    assert ref.value(d, d, mu, nu, got.plan) == pytest.approx(
        float(got.value), rel=1e-5)


def test_reference_walks_served_point_cloud_requests():
    from repro.core.geometry import PointCloudGeometry
    from repro.core.gw import GWConfig
    from repro.serve.engine import GWEngine, GWServeConfig

    probs = []
    for i, n in enumerate([12, 20, 20]):
        k = jax.random.fold_in(jax.random.PRNGKey(5), i)
        kx, ky, km, kn = jax.random.split(k, 4)
        probs.append((jax.random.uniform(kx, (n, 3), jnp.float32),
                      jax.random.uniform(ky, (n, 3), jnp.float32),
                      _measure(km, n), _measure(kn, n)))
    eng = GWEngine(GWServeConfig(solver=GWConfig(**CLOUD_SOLVER),
                                 scheduler="pipeline", max_batch=2,
                                 size_bucket=8))
    out = dict(eng.serve([(PointCloudGeometry(px), PointCloudGeometry(py),
                           mu, nu) for px, py, mu, nu in probs]))
    assert sorted(out) == [0, 1, 2]
    s = ref.Settings.of(CLOUD_SOLVER)
    for rid, (px, py, mu, nu) in enumerate(probs):
        res = out[rid]
        dx, dy = ref.sqeuclidean_distance(px), ref.sqeuclidean_distance(py)
        want = ref.solve(dx, dy, mu, nu, s)
        assert int(res.info.outer_iters) == want.outer_iters
        assert float(res.value) == pytest.approx(want.value, rel=1e-4)
        assert ref.l1(res.plan, want.plan) < 1e-4
        assert ref.marginal_gap(res.plan, mu, nu) < 1e-3


def test_reference_sinkhorn_meets_its_tolerance_and_cap():
    c = jnp.asarray(np.random.default_rng(1).random((9, 7)), jnp.float32)
    mu = jnp.full((9,), 1 / 9, jnp.float32)
    nu = jnp.full((7,), 1 / 7, jnp.float32)
    f, g, used, err = ref.sinkhorn(c, mu, nu, 0.1, 200, 25, 1e-5)
    assert err <= 1e-5 and used % 25 == 0 and used <= 200
    _, _, used, _ = ref.sinkhorn(c, mu, nu, 0.1, 30, 25, 0.0)
    assert used == 30
