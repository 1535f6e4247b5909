#!/usr/bin/env python3
"""Run one benchmark cell and print its result as the last line of stdout.

  python3 bench/run.py --workload g500-s21-ell.pagerank --seed 7 \
      --seconds 51 --trace 0

With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""

import time

T_START = time.monotonic()   # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

# The checkout's root, not this directory: bench/ holds modules (trace.py)
# whose names would shadow the standard library's.
_HERE = pathlib.Path(__file__).resolve().parent
sys.path[:] = [p for p in sys.path if pathlib.Path(p or ".").resolve() != _HERE]
sys.path.insert(0, str(_HERE.parent))
os.environ.setdefault("TPU_LOG_DIR", "disabled")   # libtpu logs nowhere else


def main(argv=None) -> int:
  ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
  ap.add_argument("--workload", required=True)
  ap.add_argument("--seed", type=int, required=True)
  ap.add_argument("--seconds", type=float, required=True)
  ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
  args = ap.parse_args(argv)
  from bench.harness import HarnessError, run_cell
  try:
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start=T_START)
  except HarnessError as e:
    print(f"bench: {e}", file=sys.stderr, flush=True)
    return 2
  sys.stderr.flush()
  print(json.dumps(result), flush=True)
  return 0


if __name__ == "__main__":
  sys.exit(main())
