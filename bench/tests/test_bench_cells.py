"""A whole run of each cell on the CPU at the tiny sizes of ``tiny_root``,
with the look for a chip skipped: correct runs read correct and report
their metrics, and a new mix, metric or configuration takes only new
files."""
import hashlib
import io
import json
import shutil

import pytest

from bench import harness, spec
from bench.tests._whole_run import CELLS, ROOT, run, write_tiny
from bench.tests.test_bench_xplane import SCOPED, _trace_dir

@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct_and_reports_its_metrics(tiny_root, cell):
    r, log = run(tiny_root, cell)
    assert r["correct"], log
    assert r["attempted"] > 0 and r["failed"] == 0
    names = {m["name"] for m in spec.load_cell(cell, tiny_root).end_to_end}
    assert set(r["metrics"]) == names and "setup_s" in names
    assert list(r)[-1] == "checks" and r["checks"]
    for c in r["checks"].values():
        assert c["value"] <= c["limit"]
    last = log.strip().splitlines()[-len(r["checks"]):]
    assert all(" limit " in line for line in last)
    json.dumps(r)


def test_a_mix_and_a_metric_are_new_files_only(tiny_root):
    """A new traffic mix and a new per-layer metric take new files under
    bench/ and new entries in BENCHMARK.json, and no edit of any file."""
    (tiny_root / "bench" / "traffic" / "pairs.json").write_text(json.dumps(
        {"kind": "waves", "why": "waves of two", "wave_size": 2}))
    (tiny_root / "bench" / "metrics" / "waves_seen.served.py").write_text(
        "def read(run):\n    return run.counters['requests'] / 2\n")
    bj = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bj["workloads"].append({"name": "cloud3d-served.pairs",
                            "config": "cloud3d-served", "traffic": "pairs",
                            "chips": 1, "why": "pairs"})
    for m in bj["end_to_end"]:
        if m["name"].startswith("served_"):
            m["workloads"].append("cloud3d-served.pairs")
    bj["per_layer"].append({"name": "waves_seen.served", "unit": "waves",
                            "better": "higher", "source": "program_counter",
                            "layer": "engine host path (serve/engine.py)",
                            "moves": "served_rps",
                            "workloads": ["cloud3d-served.pairs"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bj))
    cell = spec.load_cell("cloud3d-served.pairs", tiny_root)
    assert [m["name"] for m in cell.per_layer][-1] == "waves_seen.served"
    r, log = run(tiny_root, "cloud3d-served.pairs")
    assert r["correct"], log
    assert r["attempted"] % 2 == 0


#: A configuration with everything new: a 1D grid geometry, a driver kind
#: that times one pass over the pool, its CPU cut, its mix, and readers of
#: the traced window's scope and op seconds.  The names are the test's
#: own, so that no configuration a later change adds collides with them.
NEW_FILES = {
    "bench/geometries/probe_line.py": '''
import jax.numpy as jnp


def program(geo):
    from repro.core.grids import Grid1D

    n = int(geo["n"])
    return Grid1D(n, 1.0 / (n - 1), int(geo["k"]))


def distance(geo):
    n = int(geo["n"])
    x = jnp.arange(n, dtype=jnp.float32) / (n - 1)
    return jnp.abs(x[:, None] - x[None, :]) ** int(geo["k"])
''',
    "bench/drivers/probe_pass.py": '''
from pathlib import Path

from bench import spec

solve = spec.load_file(Path(__file__).with_name("solve.py"), "solve driver")


class Driver(solve.Driver):
    def window(self, seconds):
        return super().window(0.0)
''',
    "bench/configs/probe-line.json": json.dumps({
        "name": "probe-line",
        "geometry": {"type": "probe_line", "n": 10000, "k": 1},
        "solver": {"eps": 0.002, "eps_init": 0.05, "anneal_decay": 0.5,
                   "outer_iters": 30, "sinkhorn_iters": 200,
                   "sinkhorn_chunk": 25, "tol": 0.0001,
                   "backend": "cumsum", "sinkhorn_backend": "auto",
                   "plan": "full"},
        "precision": {"dtype": "float32", "matmul": None},
        "control": {"why": "bfloat16 cost tiles",
                    "set": {"solver": {"cost_dtype": "bf16"}}},
        "checks": {"value_gap": 0.004, "optimum_gap": 0.0006,
                   "marginal_gap": 0.0001, "outer_gap": 0.15}}),
    "bench/tests/cuts/probe-line.json": json.dumps({"geometry": {"n": 64}}),
    "bench/traffic/probe_pass.json": json.dumps(
        {"kind": "probe_pass", "why": "one pass over two pairs", "pool": 2,
         "pool_seed": 0}),
    "bench/metrics/probe_grad_share.probe.py": '''
def read(run):
    scopes = getattr(run.trace, "scope_seconds", None)
    if not scopes or "gw.grad" not in scopes:
        return None
    return 100.0 * scopes["gw.grad"] / sum(scopes.values())
''',
    "bench/metrics/probe_row_kernel_s.probe.py": '''
def read(run):
    return getattr(run.trace, "op_seconds", {}).get("%gw_sinkhorn_row")
''',
}
NEW_CELL = "probe-line.probe_pass"


def _hashes(root):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).digest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def _add_entries(bj: dict) -> dict:
    bj["configs"].append({"name": "probe-line",
                          "source": "https://arxiv.org/abs/2404.08970",
                          "file": "bench/configs/probe-line.json",
                          "reduced": [], "why": "a 1D grid"})
    bj["workloads"].append({"name": NEW_CELL, "config": "probe-line",
                            "traffic": "probe_pass", "chips": 1,
                            "why": "one pass"})
    for m in bj["end_to_end"]:
        if m["name"] == "solve_s":
            m["workloads"].append(NEW_CELL)
    for name, unit in [("probe_grad_share.probe", "%"),
                       ("probe_row_kernel_s.probe", "s")]:
        bj["per_layer"].append({"name": name, "unit": unit,
                                "better": "lower", "source": "device_trace",
                                "layer": "FGC gradient", "moves": "solve_s",
                                "workloads": [NEW_CELL]})
    return bj


def test_a_configuration_is_new_files_only(tmp_path, monkeypatch):
    """A configuration with a new driver kind, a new solve geometry type,
    its CPU cut, a new mix and readers of scope and op seconds enters as
    new files under bench/ and new entries in BENCHMARK.json: its cell runs
    correct at its cut's size and reports its metrics, the traced readers
    reading a trace recorded on the chip, and no file that the checkout had
    changes by a byte."""
    src = tmp_path / "src"
    shutil.copytree(ROOT / "bench", src / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", src / "BENCHMARK.json")
    before = _hashes(src)
    for rel, text in NEW_FILES.items():
        assert rel not in before
        (src / rel).parent.mkdir(parents=True, exist_ok=True)
        (src / rel).write_text(text)
    bj = json.loads((src / "BENCHMARK.json").read_text())
    (src / "BENCHMARK.json").write_text(json.dumps(_add_entries(bj)))

    tiny = write_tiny(tmp_path / "checkout", src)
    cell = spec.load_cell(NEW_CELL, tiny)
    assert cell.config["geometry"]["n"] == 64
    r, log = run(tiny, NEW_CELL)
    assert r["correct"], log
    assert r["attempted"] == 2 and r["failed"] == 0
    assert set(r["metrics"]) == {"solve_s", "setup_s"}

    real = harness.read_trace
    monkeypatch.setattr(harness, "read_trace", lambda directory, log:
                        real(_trace_dir(tmp_path / "trace", SCOPED), log))
    r, log = run(tiny, NEW_CELL, trace=True)
    assert r["correct"], log
    reduced = real(_trace_dir(tmp_path / "again", SCOPED), io.StringIO())
    grad = reduced.scope_seconds["gw.grad"]
    assert r["metrics"] == {
        "probe_grad_share.probe": {
            "value": 100.0 * grad / sum(reduced.scope_seconds.values()),
            "unit": "%"},
        "probe_row_kernel_s.probe": {
            "value": reduced.op_seconds["%gw_sinkhorn_row"], "unit": "s"}}
    assert r["breakdown"]["device_ops"] == reduced.device_ops

    after = _hashes(src)
    del before["BENCHMARK.json"]
    assert {k: after[k] for k in before} == before
