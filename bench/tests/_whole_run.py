"""Shared by the whole-run tests: one run of a cell on the CPU at the tiny
sizes of ``tiny_root``, with the look for a chip skipped."""
import io
import time

import jax

from bench.harness import run_cell

CELLS = ["grid2d-128.solve", "cloud3d-served.unique", "cloud3d-served.zipf"]
PEAKS = {"hbm_bytes_per_s": 819e9}
SEED = 2 ** 33 + 5          # above 32 bits, as the driver's seeds are

#: Entries of cells whose files are in bench/ but which BENCHMARK.json may
#: not list yet (PERF.md, Open questions): what a later PR adds to run
#: them.  `list_pending` adds each one that is missing.
SERVED = ["cloud3d-served.unique", "cloud3d-served.zipf"]
PENDING = {
    "configs": [
        {"name": "cloud3d-served", "source": "https://arxiv.org/abs/2106.01128",
         "file": "bench/configs/cloud3d-served.json", "reduced": [],
         "why": "point clouds served through GWEngine"}],
    "workloads": [
        {"name": "cloud3d-served.unique", "config": "cloud3d-served",
         "traffic": "unique", "chips": 1, "why": "waves of new clouds"},
        {"name": "cloud3d-served.zipf", "config": "cloud3d-served",
         "traffic": "zipf", "chips": 1, "why": "waves from a Zipf pool"}],
    "end_to_end": [
        {"name": "served_rps", "unit": "requests/s", "better": "higher",
         "bound": 0.25, "source": "host_clock", "workloads": SERVED},
        {"name": "served_p95_s", "unit": "s", "better": "lower",
         "bound": 0.25, "source": "host_clock", "workloads": SERVED}],
    "per_layer": [
        {"name": "lane_useful_share.served", "unit": "%", "better": "higher",
         "source": "program_counter",
         "layer": "engine host path (serve/engine.py)", "moves": "served_rps",
         "workloads": SERVED},
        {"name": "cache_reuse_share.served", "unit": "%", "better": "higher",
         "source": "program_counter",
         "layer": "plan cache (serve/cache.py)", "moves": "served_rps",
         "workloads": ["cloud3d-served.zipf"]},
        {"name": "served_hbm_roofline.served", "unit": "%",
         "better": "higher", "source": "program_counter",
         "layer": "vmapped Sinkhorn lanes", "moves": "served_rps",
         "workloads": SERVED},
        {"name": "device_idle_share.served", "unit": "%", "better": "lower",
         "source": "device_trace", "layer": "device", "moves": "served_rps",
         "workloads": SERVED}],
}


def list_pending(bench: dict) -> dict:
    """``bench`` (a BENCHMARK.json object) with the pending entries added:
    a missing entry whole, and a present one's missing cells."""
    for key in ("configs", "workloads"):
        have = {e["name"] for e in bench[key]}
        bench[key] += [e for e in PENDING[key] if e["name"] not in have]
    for key in ("end_to_end", "per_layer"):
        have = {e["name"]: e for e in bench[key]}
        for e in PENDING[key]:
            if e["name"] not in have:
                bench[key].append(dict(e, workloads=list(e["workloads"])))
                continue
            cells = have[e["name"]]["workloads"]
            cells += [c for c in e["workloads"] if c not in cells]
    return bench


def run(root, cell, seconds=0.3, **kw):
    log = io.StringIO()
    r = run_cell(cell, SEED, seconds, False, t0=time.perf_counter(),
                 root=root, devs=jax.devices(), peaks=PEAKS, log=log, **kw)
    return r, log.getvalue()
