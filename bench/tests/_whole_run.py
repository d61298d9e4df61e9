"""Shared by the whole-run tests: one run of a cell on the CPU at the tiny
sizes of ``tiny_root``, with the look for a chip skipped."""
import io
import time

import jax

from bench.harness import run_cell

CELLS = ["grid2d-128.solve", "cloud3d-served.unique", "cloud3d-served.zipf"]
PEAKS = {"hbm_bytes_per_s": 819e9}
SEED = 2 ** 33 + 5          # above 32 bits, as the driver's seeds are

#: The cell whose files are in bench/ but which BENCHMARK.json does not
#: list yet (PERF.md, Open questions), and what a later PR adds to run it:
#: its entry, the metric only it reads, and every metric of the served cell
#: it joins.  `list_pending` adds whatever is missing.
PENDING_CELL = {"name": "cloud3d-served.zipf", "config": "cloud3d-served",
                "traffic": "zipf", "chips": 1, "why": "waves from a Zipf pool"}
PENDING_METRICS = [
    {"name": "cache_reuse_share.served", "unit": "%", "better": "higher",
     "source": "program_counter", "layer": "plan cache (serve/cache.py)",
     "moves": "served_rps", "workloads": [PENDING_CELL["name"]]}]
JOINS = "cloud3d-served.unique"


def list_pending(bench: dict) -> dict:
    """``bench`` (a BENCHMARK.json object) with the pending cell and its
    metrics added."""
    cell = PENDING_CELL["name"]
    if all(w["name"] != cell for w in bench["workloads"]):
        bench["workloads"].append(dict(PENDING_CELL))
    for key in ("end_to_end", "per_layer"):
        for e in bench[key]:
            cells = e.get("workloads")
            if cells is not None and JOINS in cells and cell not in cells:
                cells.append(cell)
    have = {e["name"] for e in bench["per_layer"]}
    bench["per_layer"] += [dict(e, workloads=list(e["workloads"]))
                           for e in PENDING_METRICS if e["name"] not in have]
    return bench


def run(root, cell, seconds=0.3, **kw):
    log = io.StringIO()
    r = run_cell(cell, SEED, seconds, False, t0=time.perf_counter(),
                 root=root, devs=jax.devices(), peaks=PEAKS, log=log, **kw)
    return r, log.getvalue()
