"""A whole run of each cell on the CPU at the tiny sizes of ``tiny_root``,
with the look for a chip skipped: correct runs read correct and report
their metrics, and a new mix or metric takes only new files."""
import json

import pytest

from bench import spec
from bench.tests._whole_run import CELLS, run

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
