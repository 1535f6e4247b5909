"""The benchmark's harness: finds a cell's parts by name and runs it.

Everything a cell is made of is found by the names in ``BENCHMARK.json``
and in the files they name; no registry lists them:

* ``bench/configs/<config>.json``: the deployment (graph, container, plan,
  server, algorithm settings);
* ``bench/containers/<container>.py``: ``build(arcs, spec)`` puts the arcs
  into the program's container named by the config's ``graph.container``;
* ``bench/traffic/<traffic>.json``: the mix, read by the general driver its
  ``driver`` key names (``bench/drivers/<driver>.py``), with the
  ``algorithm`` it runs and the limits of the comparison that decides
  ``correct``;
* ``bench/programs/<algorithm>.py``: how the drivers reach the program's
  public entry for the algorithm (``Batch``; ``family`` and ``query`` for
  served queries);
* ``bench/reference/<algorithm>.py``: the plain reference, the control and
  the numbers compared (:mod:`bench.check`);
* ``bench/metrics/<metric>.py``: one reader per per-layer metric, whose
  ``read(ctx)`` returns the value, or None where it finds nothing to read;
* ``bench/peaks.json``: the device's peaks, keyed by ``device_kind``.

A run: check the device, generate the graph on the device from the seed
(or from the configuration's ``graph.seed``, relabelled by the run's seed),
build the program's container, warm up (compile) the cell's own shapes, then
measure for ``seconds`` with no compilation, read the device's peak memory,
free the program's state and compare the answers with the plain reference.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH_DIR = ROOT / "bench"
WINDOW_SPAN = "bench.window"
RUN_SPAN = "bench.run"
KERNEL_MARK = "tpu_custom_call"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

sys.path.insert(0, str(ROOT / "src"))


class HarnessError(RuntimeError):
  """A run that cannot be measured: no result line, non-zero exit."""


def load_json(path: pathlib.Path) -> Any:
  try:
    with open(path) as f:
      return json.load(f)
  except FileNotFoundError:
    raise HarnessError(f"missing {path.relative_to(ROOT)}") from None


def load_benchmark() -> dict:
  return load_json(ROOT / "BENCHMARK.json")


def cell_entry(bench: dict, name: str) -> dict:
  for w in bench["workloads"]:
    if w["name"] == name:
      return w
  raise HarnessError(f"no workload {name!r} in BENCHMARK.json")


def end_to_end_for(bench: dict, cell: str) -> List[dict]:
  return [m for m in bench["end_to_end"]
          if "workloads" not in m or cell in m["workloads"]]


def per_layer_for(bench: dict, cell: str) -> List[dict]:
  moved = {m["name"] for m in end_to_end_for(bench, cell)}
  return [m for m in bench["per_layer"]
          if (cell in m["workloads"] if "workloads" in m
              else m["moves"] in moved)]


_PARTS: Dict[pathlib.Path, Any] = {}


def load_part(kind: str, name: str):
  """``bench/<kind>/<name>.py`` as a module, loaded once (a name may hold
  dots and dashes)."""
  path = BENCH_DIR / kind / f"{name}.py"
  if path not in _PARTS:
    if not path.exists():
      raise HarnessError(f"no {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    _PARTS[path] = mod
  return _PARTS[path]


def device_peaks(kind: str) -> dict:
  table = load_json(BENCH_DIR / "peaks.json")["devices"]
  if kind not in table:
    raise HarnessError(f"device kind {kind!r} is not in bench/peaks.json")
  return table[kind]


class CompileCounter:
  """Counts the programs JAX compiles or loads from its cache.  JAX keeps
  its listeners for the life of the process, so there is one counter."""

  _instance: Optional["CompileCounter"] = None

  def __init__(self):
    import jax
    self.count = 0
    jax.monitoring.register_event_duration_secs_listener(self._on_event)

  @classmethod
  def get(cls) -> "CompileCounter":
    if cls._instance is None:
      cls._instance = cls()
    return cls._instance

  def _on_event(self, event: str, duration: float, **kwargs) -> None:
    if event == BACKEND_COMPILE_EVENT:
      self.count += 1


@dataclasses.dataclass
class Context:
  """What a driver and a per-layer reader may read."""

  cell: str
  seed: int
  config: dict
  traffic: dict
  arcs: Any = None                 # bench.gen.graph500.Arcs
  graph: Any = None                # the program's container
  plan: Any = None                 # repro.core.backends.Plan
  layout: Dict[str, Any] = dataclasses.field(default_factory=dict)
  device_kind: str = ""
  peaks: Dict[str, float] = dataclasses.field(default_factory=dict)
  measures: Dict[str, float] = dataclasses.field(default_factory=dict)
  summary: Any = None              # bench.trace.Summary of a traced run
  require_chip: bool = True


def check_kernel(fn: Callable, ctx: Context, *args) -> None:
  """On a chip, a ``pallas`` plan must put the kernel into the program that
  ``fn(*args)`` runs: its lowering must hold the Pallas custom call."""
  import jax
  if not ctx.require_chip or ctx.plan.backend != "pallas":
    return
  if KERNEL_MARK not in jax.jit(fn).lower(*args).as_text():
    raise HarnessError(f"pallas plan lowered without {KERNEL_MARK}: the "
                       "backend fell back")


def check_devices(chips: int, require_chip: bool):
  import jax
  devices = jax.devices()
  if require_chip and devices[0].platform != "tpu":
    raise HarnessError(f"needs a TPU; JAX found {devices[0].platform}")
  if len(devices) < chips:
    raise HarnessError(f"needs {chips} chips; JAX found {len(devices)}")
  return devices[:chips]


def build_graph(ctx: Context) -> None:
  """The config's Graph500 graph for the seed, in the program's container
  that the config's ``graph.container`` names."""
  import jax
  from repro.core.backends import Plan
  from bench.gen import graph500
  cfg = ctx.config
  g = cfg["graph"]
  ctx.arcs = graph500.generate(ctx.seed, scale=cfg["scale"],
                               edgefactor=g["edgefactor"],
                               abc=(g["A"], g["B"], g["C"]),
                               graph_seed=g.get("seed"))
  ctx.graph, ctx.layout = load_part("containers", g["container"]).build(
      ctx.arcs, g)
  ctx.plan = Plan(**cfg["plan"])
  jax.block_until_ready(ctx.graph)


def make_driver(ctx: Context):
  return load_part("drivers", ctx.traffic["driver"]).Driver(ctx)


def profiler_options():
  import jax
  opts = jax.profiler.ProfileOptions()
  opts.python_tracer_level = 0
  opts.enable_hlo_proto = False
  return opts


def run_cell(cell: str, seed: int, seconds: float, trace: bool, *,
             t_start: Optional[float] = None, require_chip: bool = True,
             control: bool = False) -> dict:
  """One run of ``cell``; returns the result object (raises HarnessError).

  ``control``: also compare the control's answers at the keys the run
  compared, under ``control`` (for ``bench/calibrate.py``; no benchmark run
  computes it).
  """
  t_start = time.monotonic() if t_start is None else t_start
  bench = load_benchmark()
  entry = cell_entry(bench, cell)
  ctx = Context(cell=cell, seed=int(seed), require_chip=require_chip,
                config=load_json(BENCH_DIR / "configs"
                                 / f"{entry['config']}.json"),
                traffic=load_json(BENCH_DIR / "traffic"
                                  / f"{entry['traffic']}.json"))
  import jax
  from repro.compile_cache import enable_compile_cache
  from bench import check
  from bench import trace as trace_lib

  devices = check_devices(entry["chips"], require_chip)
  ctx.device_kind = devices[0].device_kind
  if require_chip:
    ctx.peaks = device_peaks(ctx.device_kind)
  enable_compile_cache()
  jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
  # No eviction: it reads every entry's access-time file before each write,
  # so one entry copied in without it would make every write fail.
  jax.config.update("jax_compilation_cache_max_size", -1)
  counter = CompileCounter.get()

  build_graph(ctx)
  driver = make_driver(ctx)
  driver.warm_up()

  log_dir = None
  if trace:
    log_dir = tempfile.mkdtemp(prefix="bench-trace-")
    jax.profiler.start_trace(log_dir, profiler_options=profiler_options())
  compiles0 = counter.count
  setup_s = time.monotonic() - t_start
  try:
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
      driver.window(seconds)
  finally:
    if trace:
      jax.profiler.stop_trace()
  in_window = counter.count - compiles0
  driver.finish()
  if in_window:
    raise HarnessError(f"{in_window} programs compiled inside the window")
  if trace:
    try:
      ctx.summary = trace_lib.summarize(
          trace_lib.read_xplane(trace_lib.find_xplane(log_dir)))
    finally:
      shutil.rmtree(log_dir, ignore_errors=True)

  stats = [d.memory_stats() or {} for d in devices]
  peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
  e2e = driver.end_to_end()
  e2e["setup_s"] = setup_s
  ctx.measures = driver.measures()
  attempted, failed = driver.attempted, driver.failed

  driver.release()
  ctx.graph = None
  t_check = time.monotonic()
  algorithm = ctx.traffic["algorithm"]
  answers = driver.answers()
  numbers = check.compare(algorithm, answers, ctx.arcs, ctx.config,
                          driver.missing)
  correct, table = check.judge(numbers, ctx.traffic["limits"])
  print(f"reference check {time.monotonic() - t_check:.3f} s", flush=True)

  if trace:
    metrics = {}
    for m in per_layer_for(bench, cell):
      value = load_part("metrics", m["name"]).read(ctx)
      if value is not None:
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
  else:
    metrics = {}
    for m in end_to_end_for(bench, cell):
      if m["name"] not in e2e:
        raise HarnessError(f"the {ctx.traffic['driver']} driver does not "
                           f"measure {m['name']!r}")
      metrics[m["name"]] = {"value": float(e2e[m["name"]]), "unit": m["unit"]}

  device = {"platform": devices[0].platform, "kind": ctx.device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}
  result = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
  if trace:
    s = ctx.summary
    device["busy_s"] = s.busy_s
    device["window_s"] = s.window_s
    result["breakdown"] = {"device_ops": trace_lib.top(s.op_seconds),
                           "idle_gaps": [[k, v] for k, v in s.idle_gaps[:10]]}
  if control:
    result["control"] = check.judge(check.compare(
        algorithm, answers, ctx.arcs, ctx.config,
        answers_of="control"), ctx.traffic["limits"])[1]
  result["checks"] = table
  check.print_table(table)
  return result
