"""Fixtures of the benchmark's CPU tests.

Every test runs with 64-bit types off, as the benchmark does on the chip,
whatever another suite in the same process set, and with JAX's persistent
compilation cache left as it was.  ``tiny_root`` is a copy of the
benchmark with its configurations cut to CPU sizes: the same cells, mixes,
metrics and limits, run through the same code, and the cells whose files
are here but which BENCHMARK.json does not list yet.
"""
import json
import shutil
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]

#: CPU sizes of each configuration; everything else is the shipped file
TINY = {
    "grid2d-128": {"geometry": {"side": 16},
                   "solver": {"sinkhorn_backend": "pallas"}},
    "cloud3d-served": {"geometry": {"sizes": [16, 24]},
                       "solver": {"sinkhorn_backend": "pallas",
                                  "outer_iters": 60},
                       "serve": {"max_batch": 2, "size_bucket": 8}},
}
TINY_TRAFFIC = {"unique": {"wave_size": 4},
                "zipf": {"wave_size": 6, "pool": 6}}


@pytest.fixture(autouse=True)
def _x64_off_and_no_persistent_cache(monkeypatch):
    from repro.launch import compile_cache

    monkeypatch.setattr(compile_cache, "use_compile_cache", lambda: "off")
    min_s = jax.config.jax_persistent_cache_min_compile_time_secs
    with jax.enable_x64(False):
        yield
    jax.config.update("jax_persistent_cache_min_compile_time_secs", min_s)


def write_tiny(dst: Path) -> Path:
    from bench.spec import merged

    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    from bench.tests._whole_run import list_pending

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (dst / "BENCHMARK.json").write_text(json.dumps(list_pending(bench)))
    for name, cut in TINY.items():
        p = dst / "bench" / "configs" / f"{name}.json"
        p.write_text(json.dumps(merged(json.loads(p.read_text()), cut)))
    for name, cut in TINY_TRAFFIC.items():
        p = dst / "bench" / "traffic" / f"{name}.json"
        p.write_text(json.dumps(merged(json.loads(p.read_text()), cut)))
    return dst


@pytest.fixture
def tiny_root(tmp_path):
    return write_tiny(tmp_path / "checkout")
