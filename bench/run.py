"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout.  The cell, its configuration, traffic mix
and per-layer metrics are found by name from ``BENCHMARK.json``.  The last
line of standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and
last the compared numbers under ``checks``); the compared numbers also end
standard error, each beside its limit.  Exits non-zero, printing no result,
when JAX finds no TPU or fewer chips than the cell asks for, or when the
chip is not in the table of peaks.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench.harness import NoChip, run_cell

    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t0=T0, root=ROOT)
    except NoChip as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
