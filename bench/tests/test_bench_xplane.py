"""The trace reader `bench.xplane`, its scope split
(`bench.xplane.scope_seconds`) and the half-step kernel readers.

``small.xplane.pb`` is described in test_bench_trace.py.
``scoped.xplane.pb`` was recorded on one TPU v5e: one solve of the
``grid2d-128`` configuration cut to a 16×16 grid, with Pallas half-steps
and a fixed ``SCOPED_OUTER`` outer steps of 50 sweeps each (tol 0), of
pool problem 0 (seed 7), run through `bench.drivers.solve.SolveDriver`
once to warm up and once more inside one ``bench.window`` span under
``jax.profiler.trace``.
"""
import dataclasses
import importlib.util
import math
import os
import shutil
from pathlib import Path

import pytest

from bench import trace, xplane
from bench.harness import Run
from bench.spec import ROOT, load_cell, load_reader

DATA = Path(__file__).resolve().parent / "data"
SMALL = DATA / "small.xplane.pb"
SCOPED = DATA / "scoped.xplane.pb"
SCOPED_OUTER, SCOPED_INNER = 4, 200
READERS = {"gw_sinkhorn_row_roofline.solve": "%gw_sinkhorn_row",
           "gw_sinkhorn_col_roofline.solve": "%gw_sinkhorn_col"}

#: `trace.reduce` of small.xplane.pb
SMALL_REDUCED = {
    "busy_s": 0.000106484, "window_s": 0.06546945600000001,
    "idle_share": 0.9983735316206079,
    "device_ops": [["%fusion", 0.00010635500000000001],
                   ["%copy-start", 1.08e-07],
                   ["%copy-done", 2.1000000000000003e-08]],
    "idle_gaps": [["bench.window", 0.022173420000000003],
                  ["bench.window", 0.021389505],
                  ["bench.window", 0.020683241],
                  ["bench.window", 0.00032292000000000003],
                  ["bench.window", 0.00019616700000000002],
                  ["bench.window", 0.0001555],
                  ["bench.window", 0.00015097300000000002],
                  ["bench.window", 0.000149325],
                  ["bench.window", 0.0001419],
                  ["bench.window", 2e-09]]}


def _xplane_pb2():
    """The profiler's own protobuf module, loaded from its file so that
    TensorFlow itself is not imported."""
    spec = importlib.util.find_spec("tensorflow")
    path = os.path.join(list(spec.submodule_search_locations)[0], "tsl",
                        "profiler", "protobuf", "xplane_pb2.py")
    spec = importlib.util.spec_from_file_location("_xplane_pb2", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("path", [SMALL, SCOPED], ids=["small", "scoped"])
def test_events_equal_profile_data_to_the_nanosecond(path):
    from jax.profiler import ProfileData

    space = xplane.read(str(path))
    want = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith(xplane.DEVICE_PREFIX):
            want[plane.name] = {
                line.name: [(e.name, e.start_ns, e.duration_ns)
                            for e in line.events] for line in plane.lines}
    got = {p: {n: [(e.name, e.start_ns, e.duration_ns) for e in evs]
               for n, evs in lines.items()}
           for p, lines in space.planes.items()}
    assert got == want
    assert sum(len(evs) for lines in got.values()
               for evs in lines.values()) > 0


@pytest.mark.parametrize("path", [SMALL, SCOPED], ids=["small", "scoped"])
def test_stats_equal_the_protobuf_oracle(path):
    pb2 = _xplane_pb2()
    space = pb2.XSpace()
    space.ParseFromString(path.read_bytes())
    mine = xplane.read(str(path))
    for plane in space.planes:
        if not plane.name.startswith(xplane.DEVICE_PREFIX):
            continue
        names = {k: v.name for k, v in plane.stat_metadata.items()}

        def value(stat):
            kind = stat.WhichOneof("value")
            v = getattr(stat, kind)
            return names[v] if kind == "ref_value" else v

        for line in plane.lines:
            want = []
            for e in line.events:
                meta = plane.event_metadata[e.metadata_id]
                stats = {names[s.metadata_id]: value(s) for s in meta.stats}
                want.append((meta.name, line.timestamp_ns
                             + e.offset_ps // 1000, e.duration_ps // 1000,
                             stats))
            got = [(e.name, e.start_ns, e.duration_ns, e.stats)
                   for e in mine.planes[plane.name][line.name]]
            assert got == want


def test_fusion_metadata_of_the_small_trace():
    ops = xplane.read(str(SMALL)).planes["/device:TPU:0"][trace.OPS_LINE]
    fusion = [e for e in ops if e.name.startswith("%fusion = ")]
    assert len(fusion) == 12      # three solves of four products
    for e in fusion:
        assert e.stats["tf_op"] == "jit(<lambda>)/dot_general:"
        assert e.stats["bytes_accessed"] == 12_582_912
        assert e.stats["hlo_category"] == "convolution fusion"


def test_op_names_fill_in_what_xla_made_after_lowering():
    space = xplane.read(str(SMALL))
    (pid, proto), = space.hlo.items()
    ops = space.planes["/device:TPU:0"][trace.OPS_LINE]
    assert {e.stats["program_id"] for e in ops} == {pid}
    names = xplane.op_names(proto)
    assert names["fusion"] == "jit(<lambda>)/dot_general"
    # the prefetch of the first product's operand has no op_name of its
    # own: it takes that of the product it feeds
    start = [e for e in ops if e.name.startswith("%copy-start ")][0]
    assert "tf_op" not in start.stats
    assert names["copy-start"] == "jit(<lambda>)/dot_general"


def test_scope_of_takes_the_innermost_stage():
    assert xplane.scope_of("jit(f)/gw.driver/while/body/gw.grad/mul:") == \
        "gw.grad"
    assert xplane.scope_of("jit(f)/gw.driver/while/cond/lt:") == \
        "gw.driver"
    assert xplane.scope_of("jit(f)/transpose(jvp(gw.sinkhorn))/exp") == \
        "gw.sinkhorn"
    assert xplane.scope_of("jit(f)/gw_sinkhorn_row/pallas_call") == \
        xplane.UNSCOPED
    assert xplane.scope_of(None) == xplane.UNSCOPED


def _trace_dir(tmp_path, path):
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    shutil.copy(path, d / "host.xplane.pb")
    return str(tmp_path)


def test_reduce_of_the_small_trace_reads_as_before(tmp_path):
    t = trace.load(str(SMALL))
    assert dataclasses.asdict(trace.reduce(t)) == SMALL_REDUCED
    r = trace.reduce_dir(_trace_dir(tmp_path, SMALL))
    assert dataclasses.asdict(r) == SMALL_REDUCED
    # a program without scopes: all of its device time is unscoped
    (lo, hi), = [(s, e) for s, e, n in t.spans if n == trace.WINDOW_SPAN]
    assert xplane.scope_seconds(str(SMALL), lo, hi) == \
        {xplane.UNSCOPED: pytest.approx(r.busy_s)}


def _leaf_seconds(t: trace.Trace, lo, hi) -> float:
    return 1e-9 * sum(min(e, hi) - max(s, lo)
                      for ops in t.device_ops.values()
                      for s, e, _ in trace.leaves(ops)
                      if min(e, hi) > max(s, lo))


@pytest.fixture(scope="module")
def scoped():
    t = trace.load(str(SCOPED))
    (lo, hi), = [(s, e) for s, e, n in t.spans if n == trace.WINDOW_SPAN]
    return t, lo, hi, xplane.scope_seconds(str(SCOPED), lo, hi)


def test_scopes_split_the_leaf_device_time_of_the_window(scoped):
    t, lo, hi, scopes = scoped
    assert sum(scopes.values()) == pytest.approx(_leaf_seconds(t, lo, hi),
                                                 rel=1e-12)
    assert scopes["gw.grad"] > 0 and scopes["gw.sinkhorn"] > 0
    assert set(scopes) <= {"gw.grad", "gw.sinkhorn", "gw.delta",
                           "gw.driver", "gw.value", "gw.init",
                           xplane.UNSCOPED}
    # the layout copies XLA adds carry no op_name; each takes its
    # neighbour's, looked up by its whole instruction name
    assert xplane.UNSCOPED not in scopes


def test_scoped_trace_names_the_half_step_kernels(scoped):
    t, lo, hi, _ = scoped
    r = trace.reduce(t, top=1000)
    ops = {n for n, _ in r.device_ops}
    assert {"%gw_sinkhorn_row", "%gw_sinkhorn_col"} <= ops


def test_kernel_readers_read_the_scoped_trace(scoped, tiny_root, tmp_path):
    t, lo, hi, _ = scoped
    reduced = trace.reduce(t)
    cell = load_cell("grid2d-128.solve", tiny_root)
    counters = {"outer_iters": [SCOPED_OUTER],
                "inner_iters": [SCOPED_INNER]}
    peaks = {"hbm_bytes_per_s": 819e9}
    traced = Run(cell, peaks, reduced.window_s, counters, reduced)
    untraced = Run(cell, peaks, reduced.window_s, counters, None)
    for name in READERS:
        read = load_reader(tiny_root / "bench", name)
        v = read(traced)
        assert math.isfinite(v) and 0 < v < 100, (name, v)
        assert read(untraced) is None
    # a program whose kernels have other names (the small trace's) reads
    # nothing
    r = trace.reduce_dir(_trace_dir(tmp_path, SMALL))
    bare = Run(cell, peaks, r.window_s, counters, r)
    for name in READERS:
        assert load_reader(tiny_root / "bench", name)(bare) is None


@pytest.mark.parametrize("name", list(READERS))
def test_kernel_readers_count_one_pass_per_sweep(name):
    """Two solves of 60 and 25 sweeps on a 4×4 grid (a (16, 16) plan):
    each half-step kernel reads the cost once a sweep, 85 passes, in the
    second its breakdown gives it."""
    class Cell:
        config = {"geometry": {"side": 4}}

    class Reduced:
        device_ops = [["%reduce_window_sum", 4.0], [READERS[name], 1.0],
                      ["%copy", 0.5]]
    read = load_reader(ROOT / "bench", name)
    peaks = {"hbm_bytes_per_s": 1.0}
    run = Run(Cell, peaks, 3.0, {"inner_iters": [60, 25]}, Reduced)
    assert read(run) == pytest.approx(100.0 * 85 * 4 * 16 * 16)
    # silent without the counter, the trace, or the kernel's name
    assert read(Run(Cell, peaks, 3.0, {}, Reduced)) is None
    assert read(Run(Cell, peaks, 3.0, {"inner_iters": [60]}, None)) is None

    class Renamed:
        device_ops = [["%_sinkhorn_row_update_pallas", 1.0],
                      ["%_sinkhorn_col_update_pallas", 1.0]]
    assert read(Run(Cell, peaks, 3.0, {"inner_iters": [60]}, Renamed)) \
        is None
