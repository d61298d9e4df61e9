"""Entry points: the GW serving CLI's exit code, the persistent compile
cache's placement, and `chip_smoke.py` — refusing a host without a TPU,
and each of its phases at a tiny size on the CPU."""
from __future__ import annotations

import importlib.util
import warnings
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache
from repro.launch import serve as serve_cli

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture
def gw_cli(monkeypatch):
    """`repro.launch.serve.main` for a short GW stream, with the process's
    compile cache left where it was."""
    monkeypatch.setattr(serve_cli, "use_compile_cache", lambda: None)
    return lambda: serve_cli.main(["--gw", "--requests", "3",
                                   "--cache-capacity", "0"])


def test_serve_gw_exits_zero_when_every_bucket_solves(gw_cli):
    assert gw_cli() == 0


def test_serve_gw_exits_nonzero_on_bucket_failure(gw_cli, monkeypatch,
                                                  capsys):
    from repro.serve import engine

    def fail(self):
        raise RuntimeError("injected bucket failure")

    monkeypatch.setattr(engine._BucketRun, "issue", fail)
    assert gw_cli() == 1
    assert "injected bucket failure" in capsys.readouterr().err


@pytest.fixture
def cache_dir_restored():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_leaves_env_dir_alone(monkeypatch, tmp_path,
                                            cache_dir_restored):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_repo_dir(monkeypatch, cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(REPO / ".jax_cache")
    assert compile_cache.use_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_chip_smoke_refuses_a_host_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert _chip_smoke().main([]) == 1
    out = capsys.readouterr()
    assert out.out == ""
    assert "no TPU" in out.err


TINY = {"dense": dict(side=16),
        "served": dict(sizes=(64, 128, 64, 128, 64, 128, 64, 128)),
        "factored": dict(n=1024),
        "gradient": dict(n=128)}


@pytest.mark.parametrize("phase", sorted(TINY))
def test_chip_smoke_phase_on_cpu(phase, monkeypatch):
    """Each phase end to end at a tiny size, with the kernels in interpret
    mode: what the chip run checks, minus the chip."""
    from repro.kernels import ops

    smoke = _chip_smoke()
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)   # "auto" → pallas
    check = smoke.Checks()
    with jax.enable_x64(False), warnings.catch_warnings():
        warnings.simplefilter("error")
        smoke.PHASES[phase](check, jax.random.PRNGKey(0), **TINY[phase])
    assert check.failed == []
