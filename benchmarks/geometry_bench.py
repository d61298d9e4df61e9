"""FGC vs low-rank vs dense applies across sizes and ranks — where does each
geometry win?

Run:  PYTHONPATH=src python benchmarks/geometry_bench.py [--out BENCH_geometry.json]
      (--smoke: tiny sizes so CI merely executes the perf path)

Times the solver bottleneck, the gradient product D_X Γ D_Y, through the
`GradientOperator`/`Geometry` dispatch for three cost structures of equal
size N:

  grid      GridGeometry over Grid1D (the paper's FGC apply, O(k²N²) for the
            full product — each apply is O(k²N·batch))
  lowrank   LowRankGeometry at rank r (Scetbon et al.: O(N·r) applies,
            O(N²·r) product)
  dense     PointCloudGeometry (the universal O(N²) apply, O(N³)-ish product)

Emits BENCH_geometry.json:
  product:    per (geometry, n, r) — median seconds for D_X Γ D_Y
  constant:   per (geometry, n, r) — median seconds for the C1 term
              ((D∘D)-applies: rank r² for lowrank)
  crossovers: per n, the fastest geometry; and per rank, the smallest n
              where the low-rank product beats the dense one.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmarks.common import random_measure, timeit
from repro.core import GradientOperator
from repro.core.geometry import (GridGeometry, LowRankGeometry,
                                 PointCloudGeometry)
from repro.core.grids import Grid1D
from repro.launch.compile_cache import use_compile_cache


def _geometries(n: int, rank: int, rng):
    pts = jnp.asarray(rng.normal(size=(n, 3)))
    a = jnp.asarray(rng.random(size=(n, rank)))
    return {
        "grid": GridGeometry(Grid1D(n, 1.0 / (n - 1), 1), "cumsum"),
        "lowrank": LowRankGeometry(a, a),
        "dense": PointCloudGeometry(pts),
    }


def bench(ns, ranks):
    rows_product, rows_constant = [], []
    rng = np.random.default_rng(0)
    for n in ns:
        mu = random_measure(n, 1)
        nu = random_measure(n, 2)
        gamma = mu[:, None] * nu[None, :]
        for rank in ranks:
            geoms = _geometries(n, rank, rng)
            for name, geom in geoms.items():
                if name != "lowrank" and rank != ranks[0]:
                    continue       # rank only matters for the low-rank rows
                op = GradientOperator(geom, geom)
                prod = jax.jit(lambda g, o=op: o.product(g))
                t_p, _ = timeit(prod, gamma, repeats=5)
                const = jax.jit(lambda m, v, o=op: o.constant_term(m, v)[0])
                t_c, _ = timeit(const, mu, nu, repeats=5)
                r_eff = rank if name == "lowrank" else None
                rows_product.append({"geometry": name, "n": n, "rank": r_eff,
                                     "seconds": t_p})
                rows_constant.append({"geometry": name, "n": n, "rank": r_eff,
                                      "seconds": t_c})
                tag = f"r={rank}" if name == "lowrank" else "    "
                print(f"n={n:5d} {name:8s} {tag:6s} "
                      f"product={t_p*1e6:10.1f}us  c1={t_c*1e6:9.1f}us",
                      flush=True)
    return rows_product, rows_constant


def crossovers(rows_product, ns, ranks):
    def t(name, n, rank=None):
        for r in rows_product:
            if (r["geometry"] == name and r["n"] == n
                    and r["rank"] == rank):
                return r["seconds"]
        return None

    fastest = {}
    for n in ns:
        cands = [("grid", t("grid", n)), ("dense", t("dense", n))]
        cands += [(f"lowrank_r{rk}", t("lowrank", n, rk)) for rk in ranks]
        cands = [(k, v) for k, v in cands if v is not None]
        fastest[str(n)] = min(cands, key=lambda kv: kv[1])[0]

    lowrank_beats_dense = {}
    for rk in ranks:
        win = next((n for n in ns
                    if t("lowrank", n, rk) is not None
                    and t("dense", n) is not None
                    and t("lowrank", n, rk) < t("dense", n)), None)
        lowrank_beats_dense[f"r={rk}"] = win
    return {"fastest_product_by_n": fastest,
            "lowrank_beats_dense_from_n": lowrank_beats_dense}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=str(Path(__file__).resolve().parent.parent
                                         / "BENCH_geometry.json"))
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes: execute the perf path in CI")
    args = ap.parse_args()
    use_compile_cache()
    if args.smoke:
        ns, ranks = (64, 128), (4, 8)
    else:
        ns, ranks = (256, 512, 1024, 2048, 4096), (4, 16, 64)
    rows_p, rows_c = bench(ns, ranks)
    out = {"backend": jax.default_backend(),
           "product": rows_p, "constant": rows_c,
           "crossovers": crossovers(rows_p, ns, ranks)}
    Path(args.out).write_text(json.dumps(out, indent=2) + "\n")
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
