"""One run of one cell: set-up, the measured window, the check, the result.

`run_cell` does everything between the command line and the last line of
output, so tests and `bench/readings.py` drive the same code as
``bench/run.py``.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import os
import sys
import tempfile
import time
from pathlib import Path

import jax

from bench import spec
from bench.drivers import driver, span
from bench.peaks import peaks as peaks_of

COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


_counts = collections.Counter()
_seconds = collections.Counter()
_listening = False


def _listen(name, secs, **_kw):
    _counts[name] += 1
    _seconds[name] += secs


def compiles() -> int:
    """Programs lowered for compilation in this process so far."""
    global _listening
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_listen)
        _listening = True
    return _counts[COMPILE_EVENT]


def compile_seconds() -> dict:
    """Seconds spent so far tracing, lowering, compiling and loading
    programs from the persistent cache, by JAX's event name."""
    return {k.rsplit("/", 1)[-1]: v for k, v in _seconds.items()
            if ("/compil" in k or "/jax/core/compile/" in k)
            and "saved" not in k}


@dataclasses.dataclass
class Run:
    """What a per-layer reader reads: the window's counters, its length,
    the chip's peaks and, in a traced run, the reduced trace
    (`bench.trace.Reduced`, its ``scope_seconds`` filled in)."""

    cell: spec.Cell
    peaks: dict
    window_s: float
    counters: dict
    trace: object = None


def devices_for(chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def _peak_bytes(devs):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def read_trace(directory: str, log=sys.stderr):
    """The reduced trace (`bench.trace.Reduced`) of the one ``.xplane.pb``
    under ``directory``, with the seconds of each ``gw.*`` scope over the
    same ``bench.window`` span."""
    from bench import trace, xplane

    path = trace.find_xplane(directory)
    t0 = time.perf_counter()
    loaded = trace.load(path)
    reduced = trace.reduce(loaded)
    t1 = time.perf_counter()
    reduced.scope_seconds = xplane.scope_seconds(path, *trace.window(loaded))
    scopes = " ".join(f"{k}={v!r}" for k, v in sorted(
        reduced.scope_seconds.items(), key=lambda kv: -kv[1]))
    print(f"trace: {os.path.getsize(path)} bytes reduced in {t1 - t0:.3f} s "
          f"and split by scope in {time.perf_counter() - t1:.3f} s; "
          f"device seconds by scope: {scopes}", file=log, flush=True)
    return reduced


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t0: float, root: Path = spec.ROOT, control: bool = False,
             devs=None, peaks: dict | None = None, log=sys.stderr) -> dict:
    """Run ``workload`` once and return the result's JSON object.

    ``devs`` and ``peaks`` default to the chips JAX finds and their peaks
    (raising `NoChip` or KeyError); tests pass them to run on the CPU.
    ``control`` switches on the configuration's ``control``."""
    cell = spec.load_cell(workload, root)
    if devs is None:
        devs = devices_for(cell.chips)
    if peaks is None:
        peaks = peaks_of(devs[0].device_kind)
    from repro.launch.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    config = spec.control_config(cell.config) if control else cell.config
    matmul = config["precision"].get("matmul")
    compiles()
    t_import = time.perf_counter() - t0
    with (jax.default_matmul_precision(matmul) if matmul
          else contextlib.nullcontext()):
        built = compiles()
        drv = driver(cell.traffic["kind"], root)(config, cell.traffic, seed)
        setup_s = time.perf_counter() - t0
        built = compiles() - built
        spent = " ".join(f"{k}={v:.3f}"
                         for k, v in sorted(compile_seconds().items()))
        print(f"setup: {setup_s:.3f} s: {t_import:.3f} s of imports and "
              f"device start, then {built} programs built or loaded from "
              f"{cache_dir} ({spent}) and the warm-up", file=log,
              flush=True)
        reduced = None
        before = compiles()
        if trace:
            with tempfile.TemporaryDirectory() as tdir:
                with jax.profiler.trace(tdir):
                    with span("window"):
                        win = drv.window(seconds)
                reduced = read_trace(tdir, log)
        else:
            with span("window"):
                win = drv.window(seconds)
        in_window = compiles() - before
        memory_peak = _peak_bytes(devs)
        drv.release()
        t_check = time.perf_counter()
        checks, info = drv.check()
    for line in info:
        print(f"check: {line}", file=log)
    print(f"window: {win.window_s:.3f} s, {win.attempted} attempted, "
          f"{win.failed} failed, {in_window} programs built in the window; "
          f"check took {time.perf_counter() - t_check:.3f} s", file=log)
    run = Run(cell, peaks, win.window_s, win.counters, reduced)
    if trace:
        metrics = {}
        for m in cell.per_layer:
            v = m["reader"](run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        measured = dict(win.metrics, setup_s=setup_s)
        metrics = {m["name"]: {"value": measured[m["name"]],
                               "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in measured}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    correct = (win.attempted > 0 and win.failed == 0
               and all(c.ok for c in checks))
    result = {"correct": correct, "attempted": win.attempted,
              "failed": win.failed, "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = {"device_ops": reduced.device_ops,
                               "idle_gaps": reduced.idle_gaps}
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    for c in checks:
        print(f"{c.name} {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAIL'}", file=log, flush=True)
    return result
