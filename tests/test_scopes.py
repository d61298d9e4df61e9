"""Stage scopes and kernel names (`repro.scopes`).

Each stage of a GW solve is traced under one `jax.named_scope`, which lands
in the ``op_name`` metadata of the ops it makes and so in a profiler trace.
The one-shot, batched and segmented solves share one step body, so each
compiled program carries every stage's scope; every Pallas kernel carries
its stated name.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest

from repro import scopes
from repro.core.grids import Grid2D
from repro.core.gw import (GWConfig, _init_stacked, _segment_stacked,
                           _solve_stacked, entropic_gw, stack_problems)

GRID = Grid2D(8, 1.0 / 7, 1)
CFG = GWConfig(eps=1e-2, eps_init=5e-2, outer_iters=30, sinkhorn_iters=200,
               sinkhorn_chunk=25, tol=1e-4, backend="cumsum")


def _measures():
    mu = jax.random.uniform(jax.random.PRNGKey(0), (GRID.size,)) + 1e-3
    nu = jax.random.uniform(jax.random.PRNGKey(1), (GRID.size,)) + 1e-3
    return mu / mu.sum(), nu / nu.sum()


def _one_shot():
    mu, nu = _measures()
    return jax.jit(lambda mu, nu: entropic_gw(GRID, GRID, mu, nu, CFG)
                   ).lower(mu, nu)


def _batched():
    mu, nu = _measures()
    ops, _, _ = stack_problems([(GRID, GRID, mu, nu)] * 2, CFG)
    return _solve_stacked.lower(*ops, CFG.static_key())


def _segmented():
    mu, nu = _measures()
    ops, _, _ = stack_problems([(GRID, GRID, mu, nu)] * 2, CFG)
    carry = _init_stacked(*ops[:4], CFG.static_key())
    return _segment_stacked.lower(*ops, carry, CFG.static_key(), 2)


def _serving_init():
    mu, nu = _measures()
    ops, _, _ = stack_problems([(GRID, GRID, mu, nu)] * 2, CFG)
    return _init_stacked.lower(*ops[:4], CFG.static_key())


# the segmented program takes its carry from the serving init program, so
# its own set-up is the constant term alone
@pytest.mark.parametrize("build, stages", [
    (_one_shot, scopes.STAGES), (_batched, scopes.STAGES),
    (_segmented, scopes.STAGES), (_serving_init, (scopes.INIT,))])
def test_compiled_solve_carries_every_stage_scope(build, stages):
    names = re.findall(r'op_name="([^"]*)"', build().compile().as_text())
    found = {s for n in names for s in re.findall(r"gw\.[a-z_]+", n)}
    assert found == set(stages)


def test_fgc_matmuls_keep_their_scope():
    """The grid's axes fit one tile, so each FGC D̃-apply is a matmul by a
    constant (n, n) tile; every such dot carries its stage's scope."""
    hlo = _one_shot().compile().as_text()
    n = GRID.n
    tiles = set(re.findall(
        rf"(%constant[.\d]*) = f\d+\[{n},{n}\]\S* constant\(", hlo))
    matmuls = [ln for ln in hlo.splitlines()
               if re.search(r"= \S+ (dot|convolution)\(", ln)
               and any(re.search(re.escape(c) + r"[,)]", ln) for c in tiles)]
    assert tiles and matmuls
    assert all(re.search(r'op_name="[^"]*gw\.(grad|value|init)', ln)
               for ln in matmuls)
    assert any('gw.grad' in ln for ln in matmuls)


def _pallas_names(fn) -> list:
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["name"]
            for v in eqn.params.values():
                inner = getattr(v, "jaxpr", v)
                if hasattr(inner, "eqns"):
                    yield from walk(inner)

    return list(walk(jax.make_jaxpr(fn)().jaxpr))


def _kernel_cases():
    from repro.kernels import fgc_scan, lr_step, sinkhorn_step

    c, v, w = jnp.ones((16, 16)), jnp.ones(16), jnp.ones(16)
    n, r, k = 16, 4, 3
    return {
        scopes.SINKHORN_ROW_KERNEL: (
            lambda: sinkhorn_step.sinkhorn_row_update_pallas(
                c, v, w, 0.1, interpret=True)),
        scopes.SINKHORN_COL_KERNEL: (
            lambda: sinkhorn_step.sinkhorn_col_update_pallas(
                c, v, w, 0.1, interpret=True)),
        scopes.LR_DYKSTRA_HALF_KERNEL: (
            lambda: lr_step.lr_dykstra_half_pallas(
                jnp.ones((n, r)), jnp.ones(r), jnp.ones(n), interpret=True)),
        scopes.LR_GRAM_CHAIN_KERNEL: (
            lambda: lr_step.lr_gram_chain_pallas(
                jnp.ones((n, k)), jnp.ones((n, k)), jnp.ones((n, r)),
                jnp.ones(n), interpret=True)),
        scopes.LR_GRAD_COMBINE_KERNEL: (
            lambda: lr_step.lr_grad_combine_pallas(
                jnp.ones((n, k)), jnp.ones((k, r)), jnp.ones(n),
                jnp.ones(r), jnp.ones(r), jnp.ones(r), interpret=True)),
        scopes.FGC_DTILDE_KERNEL: (
            lambda: fgc_scan.fgc_apply_dtilde_pallas(jnp.ones((n, 8)))),
        scopes.FGC_L_KERNEL: (
            lambda: fgc_scan.fgc_apply_l_pallas(jnp.ones((n, 8)))),
    }


@pytest.mark.parametrize("name", [
    scopes.SINKHORN_ROW_KERNEL, scopes.SINKHORN_COL_KERNEL,
    scopes.LR_DYKSTRA_HALF_KERNEL, scopes.LR_GRAM_CHAIN_KERNEL,
    scopes.LR_GRAD_COMBINE_KERNEL, scopes.FGC_DTILDE_KERNEL,
    scopes.FGC_L_KERNEL])
def test_pallas_kernel_has_its_stated_name(name):
    assert _pallas_names(_kernel_cases()[name]) == [name]


def test_every_pallas_call_in_kernels_is_named():
    """Each ``pl.pallas_call`` in the kernel sources passes ``name=``, and
    the names are the ones `repro.scopes` states, once each."""
    from pathlib import Path

    import repro.kernels as kernels

    calls = names = 0
    for path in Path(kernels.__path__[0]).glob("*.py"):
        src = path.read_text()
        calls += src.count("pl.pallas_call(")
        names += len(re.findall(r"name=scopes\.\w+_KERNEL", src))
    stated = [v for k, v in vars(scopes).items() if k.endswith("_KERNEL")]
    assert calls == names == len(stated) == len(set(stated)) == 7
