"""Benchmark orchestrator — one module per paper table + kernel micro +
roofline reader. Prints ``name,us_per_call,derived`` CSV rows."""
from __future__ import annotations

import argparse
import sys

import jax

jax.config.update("jax_enable_x64", True)   # paper parity needs f64


class Report:
    def __init__(self):
        self.rows_ = []

    def row(self, table, **kv):
        self.rows_.append((table, kv))
        fgc_s = kv.get("fgc_s") or kv.get("seconds")
        us = f"{fgc_s * 1e6:.1f}" if fgc_s else ""
        derived = ";".join(f"{k}={v:.3g}" if isinstance(v, float)
                           else f"{k}={v}" for k, v in kv.items()
                           if k not in ("fgc_s", "seconds"))
        print(f"{table},{us},{derived}", flush=True)

    def slopes(self, table, ns, ts_fgc, ts_dense):
        from benchmarks.common import fit_loglog_slope
        s_f = fit_loglog_slope(ns, ts_fgc)
        s_d = fit_loglog_slope(ns, ts_dense)
        print(f"{table}_complexity,,fgc_slope={s_f:.2f};"
              f"dense_slope={s_d:.2f}", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated table modules to run")
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    from benchmarks import (kernels_bench, roofline, table2_1d, table3_2d,
                            table4_timeseries, table5_digits, table6_horse)
    modules = {
        "table2": table2_1d, "table3": table3_2d,
        "table4": table4_timeseries, "table5": table5_digits,
        "table6": table6_horse, "kernels": kernels_bench,
        "roofline": roofline,
    }
    wanted = args.only.split(",") if args.only else list(modules)
    report = Report()
    print("table,us_per_call,derived")
    for name in wanted:
        modules[name].run(report)
    return 0


if __name__ == "__main__":
    sys.exit(main())
