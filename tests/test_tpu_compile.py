"""Compile the main path for a described TPU v5e — no chip attached.

Interpret mode runs every kernel body on the CPU but accepts what the TPU
compiler refuses (block layouts, primitives the Mosaic lowering lacks, a
program larger than the device).  These tests compile with
``interpret=False`` for one chip of a described ``v5e:2x2`` topology, at
the sizes `chip_smoke.py` runs: the dense Sinkhorn half-steps and the FGC
applies on a 128×128 grid (N = 16,384), the factored-plan kernels at
N = 10⁶ and rank 16, the vmapped kernels of a served bucket (8 lanes of
N = 4096), and one outer step of the dense solve, whose
`memory_analysis()` must fit the chip's 16 GB.

The topology is described inside a fixture, so only the worker that runs
this file loads the TPU compiler.  Nothing is compiled for a real device.
"""
from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

N_DENSE = 128 * 128            # Grid2D 128×128, both sides
N_LR, RANK = 1_000_000, 16     # the README's million-point recipe
COST_RANK = 5                  # squared-Euclidean cloud in R³: rank d + 2
SERVED_LANES, SERVED_N = 8, 4096   # one full bucket of the served stream
V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def chip():
    """One chip of a described v5e, with the persistent compilation cache
    off: an entry compiled for a described chip cannot be read back
    without one, and the next compile would warn."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")      # no compiler logs under /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — any failure means "cannot"
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    compilation_cache.reset_cache()
    mp.undo()


def _spec(chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _compile(fn, *args):
    with jax.enable_x64(False):
        return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def _assert_fits_v5e(compiled):
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < V5E_HBM_BYTES, mem


@pytest.mark.parametrize("cost_dtype", ["f32", "bf16"])
@pytest.mark.parametrize("half", ["row", "col"])
def test_sinkhorn_half_step_compiles(chip, half, cost_dtype):
    from repro.kernels import sinkhorn_step

    fn = {"row": sinkhorn_step.sinkhorn_row_update_pallas,
          "col": sinkhorn_step.sinkhorn_col_update_pallas}[half]
    n = N_DENSE
    _assert_kernel(_compile(
        functools.partial(fn, interpret=False, cost_dtype=cost_dtype),
        _spec(chip, (n, n)), _spec(chip, (n,)), _spec(chip, (n,)),
        _spec(chip, ())))


@pytest.mark.parametrize("half", ["row", "col"])
def test_sinkhorn_half_step_compiles_batched(chip, half):
    """The served path's form: a bucket of lanes through `jax.vmap`, with a
    per-lane ε (the kernels' grid gains a leading lane axis)."""
    from repro.kernels import sinkhorn_step

    fn = {"row": sinkhorn_step.sinkhorn_row_update_pallas_batched,
          "col": sinkhorn_step.sinkhorn_col_update_pallas_batched}[half]
    b, n = SERVED_LANES, SERVED_N
    _assert_kernel(_compile(
        functools.partial(fn, interpret=False),
        _spec(chip, (b, n, n)), _spec(chip, (b, n)), _spec(chip, (b, n)),
        _spec(chip, (b,))))


def _lowrank_case(kernel, n, batched=False):
    from repro.kernels import lr_step

    r, c = RANK, COST_RANK
    fn, shapes = {
        "dykstra_half": ("lr_dykstra_half_pallas", [(n, r), (r,), (n,)]),
        "gram_chain": ("lr_gram_chain_pallas",
                       [(n, c), (n, c), (n, r), (n,)]),
        "grad_combine": ("lr_grad_combine_pallas",
                         [(n, c), (c, r), (n,), (r,), (r,), (r,)]),
    }[kernel]
    if batched:
        return (getattr(lr_step, fn + "_batched"),
                [(SERVED_LANES, *s) for s in shapes])
    return getattr(lr_step, fn), shapes


@pytest.mark.parametrize("batched", [False, True])
@pytest.mark.parametrize("kernel", ["dykstra_half", "gram_chain",
                                    "grad_combine"])
def test_lowrank_kernel_compiles(chip, kernel, batched):
    fn, shapes = _lowrank_case(kernel, SERVED_N if batched else N_LR,
                               batched)
    _assert_kernel(_compile(functools.partial(fn, interpret=False),
                            *(_spec(chip, s) for s in shapes)))


@pytest.mark.parametrize("p", [1, 2])
@pytest.mark.parametrize("apply", ["dtilde", "l"])
def test_fgc_kernel_compiles(chip, apply, p):
    from repro.kernels import fgc_scan

    fn = {"dtilde": fgc_scan.fgc_apply_dtilde_pallas,
          "l": fgc_scan.fgc_apply_l_pallas}[apply]
    _assert_kernel(_compile(functools.partial(fn, p=p, interpret=False),
                            _spec(chip, (N_DENSE, 128))))


def test_fgc_product_is_highest_matmuls(chip):
    """D_X Γ D_Y on the 128×128 grid, the FGC `cumsum` backend: each axis
    fits one MXU tile, so the D̃-applies are float32 matmuls at HIGHEST
    precision and no prefix sum (`reduce-window`) is left."""
    from repro.core.gradient import GradientOperator
    from repro.core.grids import Grid2D

    grid = Grid2D(128, 1.0 / 127, 1)
    op = GradientOperator(grid, grid, "cumsum")
    compiled = _compile(op.product, _spec(chip, (N_DENSE, N_DENSE)))
    hlo = compiled.as_text()
    assert "reduce-window(" not in hlo
    matmuls = [ln for ln in hlo.splitlines()
               if re.search(r"= \S+ (dot|convolution)\(", ln)]
    assert matmuls
    assert all("operand_precision={highest,highest}" in ln
               for ln in matmuls)
    _assert_fits_v5e(compiled)


def test_dense_segment_step_fits_v5e(chip, monkeypatch):
    """One outer step of the dense solve at the smoke's size, with the
    backends "auto" picks on a TPU: Sinkhorn sweeps through the compiled
    kernels, and plan, cost and temporaries inside one chip's HBM."""
    from repro.core.coupling import full_init
    from repro.core.gradient import GradientOperator
    from repro.core.grids import Grid2D
    from repro.core.gw import GWConfig, gw_plan_segment
    from repro.core.solver import SolveControls, init_carry
    from repro.kernels import ops, sinkhorn_step

    # the described chip is not this process's backend, so steer the two
    # platform probes the code would otherwise answer with "cpu"
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(sinkhorn_step, "default_interpret", lambda: False)
    assert ops.resolve_sinkhorn_backend("auto") == "pallas"

    cfg = GWConfig(eps=4e-3, eps_init=5e-2, outer_iters=8,
                   sinkhorn_iters=200, tol=1e-4)
    grid = Grid2D(128, 1.0 / 127, 1)

    def step(mu, nu, ctl, carry):
        op = GradientOperator(grid, grid, cfg.backend)
        c1, _, _ = op.constant_term(mu, nu)
        return gw_plan_segment(op, c1, mu, nu, cfg, ctl, carry, segment=1)

    with jax.enable_x64(False):
        vec = _spec(chip, (N_DENSE,))
        ctl, carry = jax.eval_shape(
            lambda mu, nu: (SolveControls.from_config(cfg),
                            init_carry(full_init(mu, nu), cfg.outer_iters)),
            vec, vec)
    ctl, carry = jax.tree.map(
        lambda a: _spec(chip, a.shape, a.dtype), (ctl, carry))
    compiled = _compile(step, vec, vec, ctl, carry)
    _assert_kernel(compiled)
    _assert_fits_v5e(compiled)
