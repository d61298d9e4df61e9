"""Read a cell's compared numbers over many seeds, and its control's, in
one process: the readings each limit in a configuration's ``checks`` is
set from.

    python3 bench/readings.py --workload <cell> --seconds <s> \
        --seeds 11 12 ... [--control-seeds 21 22 23] [--out <file>]

Each seed is a whole run of the cell (set-up, a window of ``--seconds``,
the check) through the same code as ``bench/run.py``; with
``--control-seeds`` the same runs follow with the configuration's
``control`` switched on.  One JSON line per run goes to standard output
and to ``--out``.  The benchmark's own runs never run the control.
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
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench.harness import NoChip, run_cell

    out = open(args.out, "a") if args.out else None
    try:
        runs = ([(s, False) for s in args.seeds]
                + [(s, True) for s in args.control_seeds])
        for seed, control in runs:
            t0 = time.perf_counter()
            try:
                r = run_cell(args.workload, seed, args.seconds, False,
                             t0=t0, root=ROOT, control=control)
            except NoChip as e:
                print(f"readings: {e}; nothing was run", file=sys.stderr)
                return 1
            except Exception as e:  # noqa: BLE001 — a control may crash
                r = {"error": f"{type(e).__name__}: {e}"}
            line = json.dumps({"workload": args.workload, "seed": seed,
                               "control": control,
                               "run_s": time.perf_counter() - t0, **r})
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
