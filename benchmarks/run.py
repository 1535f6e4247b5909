"""Benchmark driver — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows and exits non-zero if any
section raised (its row is then ``<section>/ERROR``).

  PYTHONPATH=src python -m benchmarks.run [--quick]
"""

from __future__ import annotations

import argparse
import sys
import traceback


def main(argv=None) -> int:
  ap = argparse.ArgumentParser()
  ap.add_argument("--quick", action="store_true", help="smaller graphs")
  ap.add_argument("--skip-scaling", action="store_true")
  args = ap.parse_args(argv)
  scale = 10 if args.quick else 12
  from repro.compile_cache import enable_compile_cache
  enable_compile_cache()

  print("name,us_per_call,derived")
  sections = []

  from benchmarks import bench_algorithms
  sections.append(("fig4_table2_algorithms",
                   lambda: bench_algorithms.main(scale)))

  sections.append(("multi_query_serving",
                   lambda: bench_algorithms.multi_query(scale)))

  from benchmarks import bench_native_gap
  sections.append(("table3_native_gap",
                   lambda: bench_native_gap.main(scale)))

  from benchmarks import bench_optimizations
  sections.append(("fig7_optimizations",
                   lambda: bench_optimizations.main(scale)))

  if not args.skip_scaling:
    from benchmarks import bench_scaling
    sections.append(("fig5_scaling", bench_scaling.main))

  failed = 0
  for name, fn in sections:
    print(f"# --- {name} ---")
    try:
      for row in fn():
        print(row, flush=True)
    except Exception:
      failed += 1
      print(f"{name}/ERROR,0.0,exception", flush=True)
      traceback.print_exc()
  return 1 if failed else 0


if __name__ == "__main__":
  raise SystemExit(main())
