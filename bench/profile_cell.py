#!/usr/bin/env python3
"""Profile one benchmark cell by the program's own names, and print where
its device time and idle time go.

  python3 bench/profile_cell.py --workload g500-s21-ell.pagerank --seed 7 \
      --seconds 51

The cell is set up as ``bench/run.py`` sets it up (graph, container,
traffic, warm-up), and its window runs under the profiler with each
program's HLO recorded (:func:`bench.scopes.profiler_options`).  The last
line of stdout is a JSON object:

* ``end_to_end``: the cell's end-to-end metrics with the profiler on, for
  the cost of tracing against a ``--trace 0`` run of the same seed;
* ``busy_s``, ``window_s``; ``phase_share``: percent of the busy time per
  ``graphmat/`` phase, and ``unscoped``; ``top_ops``: the longest operations
  with their module and phase;
* ``span_s`` / ``span_n``: the served round's host spans; ``idle_s``: idle
  seconds by the innermost span over them (or ``outside``),
  ``idle_round_s`` the same by the round's phases alone;
  ``host_round_ms``: idle milliseconds under admission and retirement per
  round; ``syncs_per_round``; ``round_idle_share``: percent of the idle
  time under a round.

No reference check is made.  Without a TPU it exits non-zero.
"""

import argparse
import json
import os
import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[1]
_HERE = str(ROOT / "bench")
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != _HERE]
sys.path.insert(0, str(ROOT))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

def main(argv=None) -> int:
  ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
  ap.add_argument("--workload", required=True)
  ap.add_argument("--seed", type=int, required=True)
  ap.add_argument("--seconds", type=float, required=True)
  args = ap.parse_args(argv)

  import dataclasses
  import jax
  from bench import harness as H
  from bench import scopes
  from bench import trace as tr
  from repro.compile_cache import enable_compile_cache

  bench = H.load_benchmark()
  entry = H.cell_entry(bench, args.workload)
  ctx = H.Context(cell=args.workload, seed=args.seed,
                  config=H.load_json(H.BENCH_DIR / "configs"
                                     / f"{entry['config']}.json"),
                  traffic=H.load_json(H.BENCH_DIR / "traffic"
                                      / f"{entry['traffic']}.json"))
  try:
    devices = H.check_devices(entry["chips"], True)
  except H.HarnessError as e:
    print(f"profile: {e}", file=sys.stderr)
    return 2
  ctx.device_kind = devices[0].device_kind
  enable_compile_cache()
  jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
  jax.config.update("jax_compilation_cache_max_size", -1)
  # The scopes live in the programs' metadata, which the cache's key leaves
  # out by default: a program cached before them would profile unscoped.
  jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
  H.build_graph(ctx)
  cell = H.make_driver(ctx)
  cell.warm_up()

  log_dir = tempfile.mkdtemp(prefix="bench-profile-")
  jax.profiler.start_trace(log_dir, profiler_options=scopes.profiler_options())
  try:
    with jax.profiler.TraceAnnotation(H.WINDOW_SPAN):
      cell.window(args.seconds)
  finally:
    jax.profiler.stop_trace()
  cell.finish()
  e2e = cell.end_to_end()
  measures = cell.measures()
  cell.release()

  try:
    reading = scopes.read(tr.find_xplane(log_dir))
  finally:
    shutil.rmtree(log_dir, ignore_errors=True)
  result = {"workload": args.workload, "seed": args.seed,
            "end_to_end": e2e, "measures": measures,
            **dataclasses.asdict(reading), **scopes.numbers(reading)}
  print(json.dumps(result), flush=True)
  return 0


if __name__ == "__main__":
  sys.exit(main())
