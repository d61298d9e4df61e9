"""Shared by the whole-run tests: a copy of the benchmark cut to CPU sizes
(`write_tiny`), and one run of a cell there with the look for a chip
skipped (`run`).

A configuration's CPU cut is ``bench/tests/cuts/<configuration>.json``
and a mix's ``bench/tests/cuts/traffic/<traffic>.json``: values merged
over the shipped file, dict by dict, and nothing else changed.  Every
configuration of ``BENCHMARK.json`` has one, so no whole run on the CPU
runs a configuration at its full size."""
import io
import json
import shutil
import time
from pathlib import Path

import jax

from bench.harness import run_cell
from bench.spec import merged

ROOT = Path(__file__).resolve().parents[2]
PEAKS = {"hbm_bytes_per_s": 819e9}
SEED = 2 ** 33 + 5          # above 32 bits, as the driver's seeds are

#: The cell whose files are in bench/ but which BENCHMARK.json does not
#: list yet (PERF.md, Open questions), and what a later PR adds to run it:
#: its entry, the metric only it reads, and every metric of the cells of
#: its configuration.  `list_pending` adds whatever is missing.
PENDING_CELL = {"name": "cloud3d-served.zipf", "config": "cloud3d-served",
                "traffic": "zipf", "chips": 1, "why": "waves from a Zipf pool"}
PENDING_METRICS = [
    {"name": "cache_reuse_share.served", "unit": "%", "better": "higher",
     "source": "program_counter", "layer": "plan cache (serve/cache.py)",
     "moves": "served_rps", "workloads": [PENDING_CELL["name"]]}]


def list_pending(bench: dict) -> dict:
    """``bench`` (a BENCHMARK.json object) with the pending cell and its
    metrics added."""
    cell = PENDING_CELL["name"]
    joins = {w["name"] for w in bench["workloads"]
             if w["config"] == PENDING_CELL["config"]}
    if cell not in joins:
        bench["workloads"].append(dict(PENDING_CELL))
    for key in ("end_to_end", "per_layer"):
        for e in bench[key]:
            cells = e.get("workloads")
            if cells is not None and joins & set(cells) and cell not in cells:
                cells.append(cell)
    have = {e["name"] for e in bench["per_layer"]}
    bench["per_layer"] += [dict(e, workloads=list(e["workloads"]))
                           for e in PENDING_METRICS if e["name"] not in have]
    return bench


#: every cell of BENCHMARK.json, and the pending one
CELLS = [w["name"] for w in list_pending(
    json.loads((ROOT / "BENCHMARK.json").read_text()))["workloads"]]


def cuts_of(src: Path) -> Path:
    return src / "bench" / "tests" / "cuts"


def missing_cuts(src: Path = ROOT) -> list:
    """The configurations of ``src``'s BENCHMARK.json without a CPU cut."""
    bench = json.loads((src / "BENCHMARK.json").read_text())
    return sorted(c["name"] for c in bench["configs"]
                  if not (cuts_of(src) / f"{c['name']}.json").is_file())


def _merge_into(path: Path, cut: Path) -> None:
    path.write_text(json.dumps(merged(json.loads(path.read_text()),
                                      json.loads(cut.read_text()))))


def write_tiny(dst: Path, src: Path = ROOT) -> Path:
    """A checkout at ``dst`` of ``src``'s benchmark with the pending cell
    listed and every cut under ``src``'s ``bench/tests/cuts`` merged in:
    the same cells, mixes, metrics and limits at CPU sizes."""
    missing = missing_cuts(src)
    if missing:
        raise FileNotFoundError(f"configurations without a CPU cut under "
                                f"{cuts_of(src)}: {missing}")
    shutil.copytree(src / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = list_pending(json.loads((src / "BENCHMARK.json").read_text()))
    (dst / "BENCHMARK.json").write_text(json.dumps(bench))
    files = {c["name"]: c["file"] for c in bench["configs"]}
    for cut in sorted(cuts_of(src).glob("*.json")):
        _merge_into(dst / files[cut.stem], cut)
    for cut in sorted((cuts_of(src) / "traffic").glob("*.json")):
        _merge_into(dst / "bench" / "traffic" / cut.name, cut)
    return dst


def run(root, cell, seconds=0.3, trace=False, **kw):
    log = io.StringIO()
    r = run_cell(cell, SEED, seconds, trace, t0=time.perf_counter(),
                 root=root, devs=jax.devices(), peaks=PEAKS, log=log, **kw)
    return r, log.getvalue()
