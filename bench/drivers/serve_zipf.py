"""Served point queries from Zipf-skewed sources whose hot set moves.

The serve driver (``bench/drivers/serve.py``) with another stream of
sources.  Traffic key ``sources``: ``{"dist": "zipf", "theta": t,
"reorder_s": s}``: a source is the candidate of rank k in an order of the
vertices of nonzero degree, with k drawn with probability proportional to
``k ** -t`` (YCSB's request distribution at its constant 0.99).  The order
is drawn from the seed, and drawn anew every ``s`` seconds of the window,
so the hot set moves; set-up uses the window's first order.  Repeated
sources are what the server's result cache and coalescing are for.

``measures`` adds ``distinct_sources``, the distinct sources submitted in
the window, and ``submitted``, the queries submitted in it.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Dict, Optional

import numpy as np

from bench.harness import HarnessError, load_part

serve = load_part("drivers", "serve")


class Sources:
  """The seed's stream of Zipf-skewed sources (thread-safe)."""

  def __init__(self, spec: dict, candidates: np.ndarray, seed: int):
    if spec["dist"] != "zipf":
      raise HarnessError(f"unknown source distribution {spec['dist']!r}")
    weight = np.arange(1, candidates.size + 1, dtype=np.float64) ** -float(
        spec["theta"])
    self.cdf = np.cumsum(weight) / weight.sum()
    self.candidates = np.sort(candidates)
    self.period = float(spec["reorder_s"])
    self.seed = seed
    self.draws = np.random.default_rng([seed, 2])
    self.lock = threading.Lock()
    self.origin: Optional[float] = None    # the window's start
    self.epoch = 0
    self.order = self.order_of(0)

  def order_of(self, epoch: int) -> np.ndarray:
    return np.random.default_rng([self.seed, 4, epoch]).permutation(
        self.candidates)

  def start(self, origin: float) -> None:
    with self.lock:
      self.origin = origin

  def next(self) -> int:
    with self.lock:
      if self.origin is not None:
        epoch = max(0, int((time.perf_counter() - self.origin)
                           // self.period))
        if epoch != self.epoch:
          self.epoch, self.order = epoch, self.order_of(epoch)
      k = int(np.searchsorted(self.cdf, self.draws.random(), side="right"))
      return int(self.order[min(k, self.order.size - 1)])


class Driver(serve.Driver):
  """Closed-loop clients against one server, sources Zipf-skewed."""

  def __init__(self, ctx):
    # The serve driver is built with its own stream of distinct sources
    # over the same candidates, then given the Zipf stream in its place.
    plain = dict(ctx.traffic, sources={"dist": "distinct"})
    super().__init__(dataclasses.replace(ctx, traffic=plain))
    self.ctx = ctx
    self.sources = Sources(ctx.traffic["sources"], self.sources.order,
                           ctx.seed)

  def window(self, seconds: float) -> None:
    self.sources.start(time.perf_counter())
    super().window(seconds)

  def measures(self) -> Dict[str, float]:
    out = super().measures()
    out["submitted"] = float(len(self.timed))
    out["distinct_sources"] = float(len({r[0] for r in self.timed}))
    return out
