"""Served point queries: closed-loop clients of a ``GraphQueryServer``.

Traffic keys: ``algorithm``, whose ``bench/programs/<algorithm>.py`` gives
the server's ``family(ctx)`` and a client's ``query(source)``; ``clients``
(closed loop: each submits one query and waits for its result before the
next); ``sources``: ``{"dist": "distinct"}``, vertices of nonzero degree in
a seeded order, never repeated, so the result cache and coalescing never
fire.

The server is built from the configuration's ``plan`` and its ``server``
keys, every one of which is a ``GraphQueryServer`` keyword (an unknown key
is an error), and is driven by a ``ServerDriver`` thread.  Set-up runs the
loop until each of the first ``num_slots`` queries has completed (every
slot has turned over), then the window opens.  Latency is the client's,
from ``submit`` to ``result``.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.service import GraphQueryServer, ServerDriver

from bench.harness import HarnessError, load_part

DRAIN_S = 60.0        # how long past the close a query may still finish
SAMPLE = 24           # answers compared with the reference per run
SUBMIT_SPAN = "bench.submit"
RESULT_SPAN = "bench.result"


class Sources:
  """The seed's stream of distinct query sources (thread-safe)."""

  def __init__(self, spec: dict, candidates: np.ndarray, seed: int):
    if spec["dist"] != "distinct":
      raise HarnessError(f"unknown source distribution {spec['dist']!r}")
    self.order = np.random.default_rng([seed, 2]).permutation(candidates)
    self.lock = threading.Lock()
    self.i = 0

  def next(self) -> int:
    with self.lock:
      if self.i >= len(self.order):
        raise HarnessError("distinct sources exhausted")
      self.i += 1
      return int(self.order[self.i - 1])


class Driver:
  """Closed-loop clients against one server for the window."""

  def __init__(self, ctx):
    self.ctx = ctx
    traffic = ctx.traffic
    self.program = load_part("programs", traffic["algorithm"])
    self.server = GraphQueryServer(ctx.graph, self.program.family(ctx),
                                   backend=ctx.plan, **ctx.config["server"])
    deg = np.bincount(ctx.arcs.src, minlength=ctx.arcs.n)
    self.sources = Sources(traffic["sources"], np.flatnonzero(deg > 0),
                           ctx.seed)
    self.clients = int(traffic["clients"])
    self.records: List[list] = []     # [source, submitted, done, value, err]
    self.lock = threading.Lock()
    self.stop = threading.Event()
    self.driver: Optional[ServerDriver] = None
    self.threads: List[threading.Thread] = []
    self.ws = self.we = 0.0

  def _client(self) -> None:
    import jax
    server = self.server
    while not self.stop.is_set():
      source = self.sources.next()
      rec = [source, time.perf_counter(), None, None, None]
      with self.lock:
        self.records.append(rec)
      try:
        with jax.profiler.TraceAnnotation(SUBMIT_SPAN):
          qid = server.submit(self.program.query(source))
        with jax.profiler.TraceAnnotation(RESULT_SPAN):
          rec[3] = server.result(qid, timeout=None)
      except Exception as e:   # noqa: BLE001 -- recorded as a failed query
        rec[4] = repr(e)
      rec[2] = time.perf_counter()

  def warm_up(self) -> None:
    self.driver = ServerDriver(self.server).start()
    self.threads = [threading.Thread(target=self._client, daemon=True,
                                     name=f"bench-client-{i}")
                    for i in range(self.clients)]
    for t in self.threads:
      t.start()
    slots = self.server.num_slots
    while True:
      if self.driver.error is not None:
        raise self.driver.error
      with self.lock:
        first = self.records[:slots]
      if len(first) == slots and all(r[2] is not None for r in first):
        break
      time.sleep(0.01)
    self.stats0 = self.server.stats()

  def window(self, seconds: float) -> None:
    self.ws = time.perf_counter()
    time.sleep(seconds)
    self.we = time.perf_counter()
    self.stats1 = self.server.stats()

  def finish(self) -> None:
    """Stop the clients and wait for every query of the window."""
    self.stop.set()
    limit = self.we + DRAIN_S
    for t in self.threads:
      t.join(max(0.0, limit - time.perf_counter()))
    hung = any(t.is_alive() for t in self.threads)
    self.driver.close("abort" if hung else "drain")
    for t in self.threads:
      t.join(5.0)
    if self.driver.error is not None:
      raise self.driver.error
    with self.lock:
      self.timed = [r for r in self.records if self.ws <= r[1] < self.we]

  @property
  def attempted(self) -> int:
    return len(self.timed)

  @property
  def failed(self) -> int:
    return sum(r[4] is not None or r[2] is None or r[3] is None
               for r in self.timed)

  def end_to_end(self) -> Dict[str, float]:
    done = [r for r in self.records
            if r[2] is not None and r[4] is None and self.ws <= r[2] <= self.we]
    lat = [(r[2] - r[1]) * 1e3 if r[2] is not None and r[4] is None
           else float("inf") for r in self.timed]
    p95 = float(np.percentile(lat, 95)) if lat else float("inf")
    return {"qps": len(done) / (self.we - self.ws), "p95_ms": p95}

  def measures(self) -> Dict[str, float]:
    def delta(name):
      return (self.stats1["counters"].get(name, 0.0)
              - self.stats0["counters"].get(name, 0.0))
    h0 = self.stats0["histograms"].get("round.slot_utilization", {})
    h1 = self.stats1["histograms"]["round.slot_utilization"]
    return {"window_s": self.we - self.ws,
            "rounds": delta("rounds"),
            "supersteps": delta("supersteps"),
            "slot_util_sum": h1["sum"] - h0.get("sum", 0.0),
            "slot_util_count": h1["count"] - h0.get("count", 0),
            "cache_hits": delta("cache.hits"),
            "completed": float(sum(r[2] is not None for r in self.timed))}

  def release(self) -> None:
    self.server = None
    self.driver = None

  def answers(self) -> List[Tuple[int, np.ndarray]]:
    """A seeded sample of the window's answers: (source, vertex values)."""
    got = [(r[0], r[3]) for r in self.timed if r[3] is not None]
    if len(got) <= SAMPLE:
      return got
    pick = np.random.default_rng([self.ctx.seed, 3]).choice(
        len(got), SAMPLE, replace=False)
    return [got[i] for i in sorted(pick)]

  @property
  def missing(self) -> int:
    """Queries of the window that never answered, or answered an error."""
    return self.failed
