"""Fixtures of the benchmark's CPU tests.

Every test runs with 64-bit types off, as the benchmark does on the chip,
whatever another suite in the same process set, and with JAX's persistent
compilation cache left as it was.  ``tiny_root`` is a copy of the
benchmark with its configurations cut to CPU sizes
(`bench.tests._whole_run.write_tiny`): the same cells, mixes, metrics and
limits, run through the same code, and the cells whose files are here but
which BENCHMARK.json does not list yet.
"""
import jax
import pytest

from bench.tests._whole_run import write_tiny


@pytest.fixture(autouse=True)
def _x64_off_and_no_persistent_cache(monkeypatch):
    from repro.launch import compile_cache

    monkeypatch.setattr(compile_cache, "use_compile_cache", lambda: "off")
    min_s = jax.config.jax_persistent_cache_min_compile_time_secs
    with jax.enable_x64(False):
        yield
    jax.config.update("jax_persistent_cache_min_compile_time_secs", min_s)


@pytest.fixture
def tiny_root(tmp_path):
    return write_tiny(tmp_path / "checkout")
