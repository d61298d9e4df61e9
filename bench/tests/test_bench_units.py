"""Peaks, the byte counts behind both rooflines, the per-layer readers, the
lookups of drivers, geometries and CPU cuts by name, the generator, and
the command's refusal to run without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import generate, roofline
from bench.harness import Run
from bench.peaks import PEAKS, peaks
from bench.spec import load_cell, load_reader

ROOT = Path(__file__).resolve().parents[2]


def test_peaks_of_v5e_and_unknown_kind_raises():
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert peaks("TPU v5 lite")["bf16_flop_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks("cpu")
    assert "cpu" not in PEAKS


def test_solve_passes_match_a_hand_count():
    # 2 outer steps of 50 and 25 sweeps, checked every 25:
    #   gradients 2 × (read Γ, write C)               4
    #   half-steps 2 × 75                            150
    #   residual checks 75 / 25                        3
    #   plan writes 2, old-plan reads 2                4
    #   value read                                     1
    assert roofline.solve_passes(2, 75, 25) == 162
    assert roofline.solve_bytes(64, 64, 2, 75, 25) == 4 * 64 * 64 * 162
    # no sweep and no step: only the value's read
    assert roofline.solve_passes(0, 0, 25) == 1
    # a partly used chunk still needs its one check, and no more
    assert (roofline.solve_passes(1, 30, 25)
            - roofline.solve_passes(1, 25, 25)) == 2 * 5 + 1
    with pytest.raises(ValueError):
        roofline.solve_passes(1, -1, 25)


def test_solve_passes_never_exceed_the_passes_of_a_plain_solve():
    """Each counted pass is one that the plain algorithm makes: the XLA
    half-step reads C at least once per sweep, every residual check and
    plan assembly reads C, and each outer step forms C from Γ."""
    for outer, chunks_per_step in [(1, 1), (8, 4), (30, 8)]:
        inner = outer * chunks_per_step * 25
        plain = (outer * 2                 # gradient: read Γ, write C
                 + 2 * inner               # one read of C per half-step
                 + outer * chunks_per_step  # one read of C per check
                 + outer * 2               # assembly: read C, write Γ
                 + outer                   # delta: read old Γ
                 + 1)                      # value
        assert roofline.solve_passes(outer, inner, 25) <= plain


def test_hbm_share_is_a_percentage():
    assert roofline.hbm_share(819e9, 2.0, {"hbm_bytes_per_s": 819e9}) == 50.0


def _run(counters, trace=None, window_s=2.0):
    return Run(None, {"hbm_bytes_per_s": 819e9}, window_s, counters, trace)


def test_readers_read_counters_and_stay_silent_without_them():
    read = {n: load_reader(ROOT / "bench", n) for n in [
        "inner_sweeps.solve", "solve_hbm_roofline.solve",
        "device_idle_share.solve", "lane_useful_share.served",
        "cache_reuse_share.served", "served_hbm_roofline.served",
        "device_idle_share.served"]}
    solve = _run({"inner_iters": [800, 900],
                                      "solved_bytes": 819e9})
    assert read["inner_sweeps.solve"](solve) == 850
    assert read["solve_hbm_roofline.solve"](solve) == 50.0
    assert read["device_idle_share.solve"](solve) is None

    class Reduced:
        idle_share = 0.25
    traced = _run({}, Reduced())
    assert read["device_idle_share.solve"](traced) == 25.0
    assert read["inner_sweeps.solve"](traced) is None
    assert read["solve_hbm_roofline.solve"](traced) is None
    served = _run({
        "requests": 64, "cache_hits": 8, "cache_warm_starts": 8,
        "executed_inner": 400, "useful_inner": 300, "solved_bytes": 0})
    assert read["cache_reuse_share.served"](served) == 25.0
    assert read["lane_useful_share.served"](served) == 75.0
    assert read["served_hbm_roofline.served"](served) is None


def test_every_metric_of_benchmark_json_has_a_reader_and_a_cell():
    bj = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"] for w in bj["workloads"]}
    for m in bj["per_layer"]:
        assert callable(load_reader(ROOT / "bench", m["name"]))
        assert set(m["workloads"]) <= cells
    for w in bj["workloads"]:
        c = load_cell(w["name"])
        e2e = {m["name"] for m in c.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2 and c.per_layer


def test_drivers_and_geometries_are_found_by_name_in_the_root(tmp_path):
    """Each kind and geometry type is a file; an unknown one is an error
    that names the file looked for, in the root the run was given."""
    from bench.drivers import driver
    from bench.drivers.solve import geometry

    assert driver("solve", ROOT).__name__ == "Driver"
    assert callable(geometry({"type": "grid2d"}).distance)
    missing = tmp_path / "bench" / "drivers" / "solve.py"
    with pytest.raises(FileNotFoundError, match=re.escape(str(missing))):
        driver("solve", tmp_path)
    with pytest.raises(FileNotFoundError, match="drivers/open_loop.py"):
        driver("open_loop", ROOT)
    with pytest.raises(FileNotFoundError, match="geometries/torus.py"):
        geometry({"type": "torus"})
    with pytest.raises(FileNotFoundError, match="metrics/nothing.solve.py"):
        load_reader(ROOT / "bench", "nothing.solve")


def test_every_configuration_has_a_cpu_cut(tmp_path):
    """A configuration's whole-run tests run at the sizes of its cut under
    bench/tests/cuts; without one the tiny checkout is refused, naming it,
    so that no test runs a configuration at its full size on the CPU."""
    from bench.tests._whole_run import missing_cuts, write_tiny

    assert missing_cuts() == []
    bj = json.loads((ROOT / "BENCHMARK.json").read_text())
    bj["configs"].append(dict(bj["configs"][0], name="uncut"))
    src = tmp_path / "src"
    shutil.copytree(ROOT / "bench", src / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (src / "BENCHMARK.json").write_text(json.dumps(bj))
    assert missing_cuts(src) == ["uncut"]
    with pytest.raises(FileNotFoundError, match="uncut"):
        write_tiny(tmp_path / "tiny", src)


def _waves(seed, mix="zipf", stream=generate.WINDOW, n=4, **cut):
    traffic = json.loads((ROOT / "bench" / "traffic" / f"{mix}.json")
                         .read_text())
    traffic.update(cut)
    w = generate.Waves(traffic, [1024, 2048, 4096], 3, seed, stream)
    return [[(r.problem, r.size, r.variant,
              None if r.rotation is None else r.rotation.tobytes())
             for r in w.wave()] for _ in range(n)]


@pytest.mark.parametrize("mix", ["unique", "zipf"])
def test_mix_gives_the_same_work_for_a_seed_and_other_work_for_another(mix):
    """Without ``problem_seed`` (the unique mix's is taken out here) the
    run's seed draws the waves."""
    big = 2 ** 33 + 1

    def waves(seed, stream=generate.WINDOW):
        return _waves(seed, mix, stream, problem_seed=None)

    assert waves(big) == waves(big)
    assert waves(big) != waves(big + 1)
    assert waves(big) != waves(big, generate.WARMUP)


def test_zipf_mix_repeats_some_problems_both_ways():
    reqs = [r for wave in _waves(7, n=8) for r in wave]
    kinds = {v for _, _, v, _ in reqs}
    assert kinds == {"first", "same", "rotated"}
    assert max(p for p, *_ in reqs) < 64
    sizes = {p: s for p, s, *_ in reqs}
    assert all(sizes[p] == s for p, s, *_ in reqs)


def test_unique_mix_never_repeats_a_problem():
    reqs = [r for wave in _waves(7, "unique", n=4) for r in wave]
    assert len({p for p, *_ in reqs}) == len(reqs) == 128
    assert {s for _, s, *_ in reqs} == {1024, 2048, 4096}


def test_unique_mix_sends_every_seed_the_same_waves():
    a = _waves(11, "unique")
    assert a == _waves(2 ** 33 + 12, "unique")
    for wave in a:
        sizes = sorted(s for _, s, *_ in wave)
        assert sizes == sorted([1024] * 11 + [2048] * 11 + [4096] * 10)
    assert len({tuple(s for _, s, *_ in w) for w in a}) == len(a)


def test_problem_seed_draws_the_whole_stream():
    """Problems, sizes and order all follow ``problem_seed``; the warm-up
    stream stays apart."""
    a = _waves(11, "unique", problem_seed=5)
    assert a == _waves(12, "unique", problem_seed=5)
    assert a != _waves(11, "unique", problem_seed=6)
    assert a == _waves(5, "unique", problem_seed=None)
    assert a != _waves(11, "unique", generate.WARMUP, problem_seed=5)


def test_rotations_are_rotations():
    r = generate.rng(3, 0)
    for _ in range(5):
        q = generate.random_rotation(r, 3)
        np.testing.assert_allclose(q @ q.T, np.eye(3), atol=1e-12)
        assert np.linalg.det(q) == pytest.approx(1.0)


def test_solve_measures_follow_the_seed():
    """The seed orders the solve mix's pool; every seed solves the same
    pool, each pass over it whole."""
    import itertools

    from bench.drivers.solve import _measures, pool_order

    def order(seed, n=12):
        return list(itertools.islice(pool_order(seed, 3), n))

    big = 2 ** 40 + 3
    assert order(big) == order(big)
    assert order(big) != order(big + 1)
    for seed in (big, big + 1, 3):
        o = order(seed)
        assert all(sorted(o[k:k + 3]) == [0, 1, 2] for k in range(0, 12, 3))
    key = jax.random.PRNGKey(generate.key_words(0, generate.WINDOW))
    a, b = (np.asarray(_measures(key, j, 64)[0]) for j in (0, 1))
    np.testing.assert_array_equal(a, np.asarray(_measures(key, 0, 64)[0]))
    assert not np.array_equal(a, b)
    assert a.sum() == pytest.approx(1.0, rel=1e-6)


def _run_py(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "grid2d-128.solve",
         "--seed", "1", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    p = _run_py(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_from_the_benchmark_files_alone_exits_nonzero(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "grid2d-128.solve",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
