#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, at a cell's size.

For each seed, in one process, a whole run of the cell through
``bench.harness.run_cell`` (a short window at the cell's own load), whose
``checks`` are the program's readings; with ``--control``, the same run
also compares the control, the reference one precision below, at the keys
the run compared (the control's readings).  One JSON line per seed:

  python3 bench/calibrate.py --cell g500-s21-ell.sssp --seeds 11 12 13 \
      --seconds 10 --control

Needs the chip, as a run does; the benchmark's own runs never compute the
control.
"""

import argparse
import json
import pathlib
import sys

_HERE = pathlib.Path(__file__).resolve().parent
sys.path[:] = [p for p in sys.path if pathlib.Path(p or ".").resolve() != _HERE]
sys.path.insert(0, str(_HERE.parent))


def main(argv=None) -> int:
  ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
  ap.add_argument("--cell", required=True)
  ap.add_argument("--seeds", nargs="+", type=int, required=True)
  ap.add_argument("--seconds", type=float, default=1.0)
  ap.add_argument("--control", action="store_true")
  args = ap.parse_args(argv)

  from bench.harness import run_cell
  for seed in args.seeds:
    r = run_cell(args.cell, seed, args.seconds, False, control=args.control)
    out = {"seed": seed, "cell": args.cell, "correct": r["correct"],
           "attempted": r["attempted"], "metrics": r["metrics"],
           "memory_peak_bytes": r["device"]["memory_peak_bytes"],
           "checks": r["checks"]}
    if args.control:
      out["control"] = r["control"]
    print(json.dumps(out), flush=True)
  return 0


if __name__ == "__main__":
  sys.exit(main())
