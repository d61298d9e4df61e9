"""Find a cell's configuration, traffic mix and per-layer readers by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found from ``BENCHMARK.json``:

    configuration  the ``file`` of its ``configs`` entry (JSON)
    traffic mix    ``bench/traffic/<traffic>.json``
    metric reader  ``bench/metrics/<metric name>.py``, with ``read(run)``

so a new cell, mix or metric is new files and new entries, never an edit.
"""
from __future__ import annotations

import copy
import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    #: end-to-end metric entries of BENCHMARK.json that this cell reports
    end_to_end: list
    #: per-layer metric entries, each with its loaded ``reader``
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_reader(bench: Path, name: str):
    """The ``read(run)`` function of ``bench/metrics/<name>.py``."""
    path = bench / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    if spec is None:
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload)]
    moved = {m["name"] for m in e2e}
    per_layer = []
    for m in bench["per_layer"]:
        if m["moves"] in moved and _applies(m, workload):
            m = dict(m, reader=load_reader(root / "bench", m["name"]))
            per_layer.append(m)
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer)


def merged(base: dict, override: dict) -> dict:
    """``base`` with ``override`` merged in, dict by dict."""
    out = copy.deepcopy(base)
    for k, v in override.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = merged(out[k], v)
        else:
            out[k] = copy.deepcopy(v)
    return out


def control_config(config: dict) -> dict:
    """The configuration with its ``control`` switched on: the same run one
    precision step below what the configuration states."""
    return merged(config, config["control"]["set"])
