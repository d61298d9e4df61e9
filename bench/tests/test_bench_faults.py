"""The control and the planted faults of each cell, driven through a whole
run on the CPU at the tiny sizes of ``tiny_root``: each reads not correct."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

from bench.spec import merged
from bench.tests._whole_run import run
from bench.tests.test_bench_window import FULL_WIDTHS

@pytest.mark.parametrize("cell", ["grid2d-128.solve",
                                  "cloud3d-served.unique"])
def test_control_reads_not_correct(tiny_root, cell):
    """The configuration's control (bfloat16 cost tiles) fails a check."""
    r, log = run(tiny_root, cell, control=True)
    assert not r["correct"], log
    # a grid solve that stops at its outer cap short of the tolerance fails
    assert r["failed"] == (r["attempted"] if cell.startswith("grid") else 0)
    assert any(c["value"] > c["limit"] for c in r["checks"].values())


def _step_unchanged(monkeypatch):
    from repro.core import gw

    def gw_step_fn(op, c1, mu, nu, cfg):
        def step(state, eps, inner_tol):
            return state, jnp.zeros((), mu.dtype), jnp.zeros((), jnp.int32)
        return step

    monkeypatch.setattr(gw, "gw_step_fn", gw_step_fn)


def _answer_altered(monkeypatch):
    from repro.core import gw
    from repro.serve import engine

    real = gw._result_of

    def _result_of(coupling, value, marginal_err, errs, info):
        return real(coupling, value * 1.1, marginal_err, errs, info)

    monkeypatch.setattr(gw, "_result_of", _result_of)
    monkeypatch.setattr(engine, "_result_of", _result_of)


def _half_the_answers_dropped(monkeypatch):
    from repro.serve import engine

    real = engine.GWEngine.serve

    def serve(self, source):
        for rid, res in real(self, source):
            if rid % 2 == 0:
                yield rid, res

    monkeypatch.setattr(engine.GWEngine, "serve", serve)


FAULTS = {"step_unchanged": _step_unchanged,
          "answer_altered": _answer_altered,
          "half_dropped": _half_the_answers_dropped}


@pytest.mark.parametrize("cell,fault", [
    ("grid2d-128.solve", "step_unchanged"),
    ("grid2d-128.solve", "answer_altered"),
    ("cloud3d-served.unique", "step_unchanged"),
    ("cloud3d-served.unique", "answer_altered"),
    ("cloud3d-served.unique", "half_dropped"),
])
def test_planted_fault_reads_not_correct(tiny_root, monkeypatch, cell,
                                         fault):
    jax.clear_caches()
    FAULTS[fault](monkeypatch)
    try:
        r, log = run(tiny_root, cell)
    finally:
        jax.clear_caches()
    assert not r["correct"], log


def test_one_lane_altered_reads_not_correct(tiny_root, monkeypatch):
    """Only the answers harvested from a lane past the first of a slot
    batch are wrong, at the configuration's own ``max_batch`` and one tiny
    size, so that each wave's run starts wide: the check sees every lane
    position, not a sample of sizes."""
    from repro.serve import engine

    p = tiny_root / "bench" / "configs" / "cloud3d-served.json"
    p.write_text(json.dumps(merged(json.loads(p.read_text()), FULL_WIDTHS)))
    real = engine.GWEngine._harvest

    def _harvest(self, carry, values, i, req):
        res = real(self, carry, values, i, req)
        return (dataclasses.replace(res, value=res.value * 1.1) if i > 0
                else res)

    monkeypatch.setattr(engine.GWEngine, "_harvest", _harvest)
    r, log = run(tiny_root, "cloud3d-served.unique")
    assert r["failed"] == 0, log
    assert r["checks"]["solve_gap"]["value"] > 0.05, log
