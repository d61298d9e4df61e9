"""The measured window holds only the cell's own work: every program it
runs was built during set-up, and a window ends with a whole pass (over
a solve cell's pool, or of a waves mix's ``pass_waves`` waves), so every
seed times the same work."""
import json
from pathlib import Path

import pytest

from bench import harness
from bench.spec import merged
from bench.tests._whole_run import run

ROOT = Path(__file__).resolve().parents[2]

ADMITTED = [w["name"] for w in
            json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
#: the served configuration's own slot widths (1, 2, 4, ... max_batch) at
#: one tiny size, besides the tiny root's 1 and 2
FULL_WIDTHS = {"geometry": {"sizes": [16]}, "serve": {"max_batch": json.loads(
    (ROOT / "bench" / "configs" / "cloud3d-served.json").read_text()
)["serve"]["max_batch"]}}


def _count_window_builds(monkeypatch) -> list:
    """Programs built inside each window the harness opens, by
    `harness.compiles`."""
    built = []
    real = harness.driver

    def driver(kind, root):
        cls = real(kind, root)

        class Counted(cls):
            def window(self, seconds):
                before = harness.compiles()
                win = super().window(seconds)
                built.append(harness.compiles() - before)
                return win

        return Counted

    monkeypatch.setattr(harness, "driver", driver)
    return built


@pytest.mark.parametrize("cell,cut", [(c, None) for c in ADMITTED] + [
    ("cloud3d-served.unique", FULL_WIDTHS)])
def test_window_builds_no_program(tiny_root, monkeypatch, cell, cut):
    if cut is not None:
        p = tiny_root / "bench" / "configs" / "cloud3d-served.json"
        p.write_text(json.dumps(merged(json.loads(p.read_text()), cut)))
    built = _count_window_builds(monkeypatch)
    r, log = run(tiny_root, cell, seconds=2.0)
    assert r["correct"], log
    assert built == [0], log


def test_solve_window_ends_with_a_pass_over_the_pool(tiny_root):
    pool = json.loads(
        (tiny_root / "bench" / "traffic" / "solve.json").read_text())["pool"]
    r, log = run(tiny_root, "grid2d-128.solve", seconds=0.0)
    assert r["correct"], log
    assert r["attempted"] == pool


def test_waves_window_ends_with_a_whole_pass(tiny_root):
    traffic = json.loads(
        (tiny_root / "bench" / "traffic" / "unique.json").read_text())
    assert traffic["pass_waves"] > 1
    r, log = run(tiny_root, "cloud3d-served.unique", seconds=0.0)
    assert r["correct"], log
    assert r["attempted"] == traffic["pass_waves"] * traffic["wave_size"]
