"""Production mesh factory.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before first init).

Real-TPU launch flags that matter at this scale (recorded here; the CPU
container exercises compile-only):
  --xla_tpu_enable_latency_hiding_scheduler=true   (overlap comm/compute)
  --xla_tpu_spmd_rng_bit_generator_unsafe=true
  megascale transport for the `pod` axis (DCN) vs ICI within a pod.
"""
from __future__ import annotations

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Elastic variant: any factorization of the available device count
    (``--mesh 8x4 --axes data,model``)."""
    assert int(np.prod(shape)) == len(jax.devices()), (
        shape, len(jax.devices()))
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def local_mesh(n_data: int = 1, n_model: int = 1):
    """Small mesh over however many devices this process sees (tests)."""
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
