"""``grid2d`` solve geometries: the unit square's ``side`` × ``side``
uniform grid, Manhattan distance to the power ``k``, points in row-major
order (the paper's 2D grid, arXiv 2404.08970 §3.2)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def program(geo: dict):
    """The geometry the program solves on."""
    from repro.core.grids import Grid2D

    side = int(geo["side"])
    return Grid2D(side, 1.0 / (side - 1), int(geo["k"]))


@partial(jax.jit, static_argnames=("side", "k"))
def _distance(side: int, k: int) -> jax.Array:
    h = 1.0 / (side - 1)
    a = jnp.repeat(jnp.arange(side, dtype=jnp.float32), side) * h
    b = jnp.tile(jnp.arange(side, dtype=jnp.float32), side) * h
    d = jnp.abs(a[:, None] - a[None, :]) + jnp.abs(b[:, None] - b[None, :])
    return d ** k


def distance(geo: dict) -> jax.Array:
    """The reference's dense (side², side²) float32 distance matrix, built
    from the grid's coordinates in plain ``jax.numpy``."""
    return _distance(int(geo["side"]), int(geo["k"]))
