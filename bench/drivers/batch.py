"""Batch analytics: whole algorithm runs back to back on one graph.

Traffic keys: ``algorithm``, whose ``bench/programs/<algorithm>.py``
``Batch`` reaches the program's public entry and says what a run counts,
and that algorithm's own parameters (SSSP: ``roots``).

Each run is one call of the program's entry, compiled in set-up; a run
ends at ``block_until_ready`` of its result.
"""

from __future__ import annotations

import time
from typing import Dict, Hashable, List, Tuple

import jax
import numpy as np

from bench.harness import RUN_SPAN, check_kernel, load_part


class Driver:
  """Runs one algorithm back to back for the window."""

  def __init__(self, ctx):
    self.ctx = ctx
    self.algo = load_part("programs", ctx.traffic["algorithm"]).Batch(ctx)
    self.runs: List[tuple] = []    # (key, output) of the window
    self.t0 = self.t1 = 0.0

  def warm_up(self) -> None:
    graph, key = self.ctx.graph, self.algo.warm_key
    check_kernel(self.algo.call, self.ctx, graph, key)
    jax.block_until_ready(self.algo.call(graph, key))

  def window(self, seconds: float) -> None:
    graph, algo, keys = self.ctx.graph, self.algo, self.algo.keys
    self.t0 = time.perf_counter()
    end = self.t0 + seconds
    i = 0
    while time.perf_counter() < end:
      key = keys[i % len(keys)]
      with jax.profiler.TraceAnnotation(RUN_SPAN):
        self.runs.append((key, jax.block_until_ready(algo.call(graph, key))))
      i += 1
    self.t1 = time.perf_counter()

  def finish(self) -> None:
    """Results to the host, so that the program's state can go."""
    self.results = [(k, np.asarray(out)) for k, out in self.runs]
    self.runs = []

  @property
  def attempted(self) -> int:
    return len(self.results)

  failed = 0
  missing = 0

  def end_to_end(self) -> Dict[str, float]:
    arcs = sum(self.algo.arcs_traversed(k, a) for k, a in self.results)
    return {"teps": arcs / (self.t1 - self.t0)}

  def measures(self) -> Dict[str, float]:
    out = {"run_s": self.t1 - self.t0, "runs": float(len(self.results))}
    steps = [self.algo.supersteps(k, a) for k, a in self.results]
    if steps and None not in steps:
      out["supersteps"] = float(sum(steps))
    return out

  def release(self) -> None:
    self.algo = None

  def answers(self) -> List[Tuple[Hashable, np.ndarray]]:
    """Every answer of the window: (key, vertex values)."""
    return self.results
