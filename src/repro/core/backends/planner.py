"""Partition-aware planner: graph statistics → execution plan.

GraphMat's thesis is that the *framework* maps vertex programs onto the best
sparse-matrix execution strategy.  The planner is that mapping, made
explicit: :func:`compute_stats` measures the graph host-side (n, nnz, degree
skew, ELL slot efficiency), :meth:`Planner.plan` applies documented
heuristics, and :meth:`Planner.autotune` replaces the heuristics with
measurement — timing candidate plans and memoizing the winner in a
:class:`PlanCache` keyed by the graph fingerprint
(:func:`repro.service.cache.graph_fingerprint`), so a server re-plans for
free when it sees a graph snapshot it has tuned before.

Heuristic table (see README "Backends & planning"):

  container   condition                                     → plan
  ---------   -------------------------------------------   -------------
  DenseGraph  always                                        dense
  EllGraph    kernel-shape-eligible & slot eff ≥ floor      pallas
  EllGraph    otherwise                                     ell
  CooGraph    scatter-fast monoid & hub ratio ≥ threshold   coo_tiled(T)
  CooGraph    otherwise                                     coo

with T = clamp(nnz / tile_edges, 2, max_tiles) equal-size edge tiles (the
paper's partitions ≫ threads, as static shapes).
"""

from __future__ import annotations

import dataclasses
import threading
import time
import warnings
from typing import (Any, Callable, Dict, Hashable, List, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import graph as graphlib
from repro.core.backends.pallas import kernel_takes
from repro.core.backends.plan import Plan
from repro.core.vertex_program import GraphProgram


@dataclasses.dataclass(frozen=True)
class GraphStats:
  """Host-side structural statistics driving plan selection."""

  container: str        # "dense" | "coo" | "ell"
  n: int                # vertices
  nnz: int              # real (unpadded) edges
  avg_degree: float     # nnz / n (in-degree mean)
  max_degree: int       # max in-degree
  degree_cv: float      # in-degree coefficient of variation (std / mean)
  hub_ratio: float      # max / mean in-degree — the skew signal
  density: float        # nnz / n²
  ell_width: int = 0            # ELL slot width (EllGraph only)
  ell_efficiency: float = 0.0   # packed nnz / (n_pad · width)


def _degree_stats(in_deg: np.ndarray):
  mean = float(in_deg.mean()) if in_deg.size else 0.0
  mx = int(in_deg.max(initial=0))
  cv = float(in_deg.std() / mean) if mean > 0 else 0.0
  hub = float(mx / mean) if mean > 0 else 1.0
  return mean, mx, cv, hub


def compute_stats(graph) -> GraphStats:
  """Measure a *concrete* graph container (host transfer; not traceable).

  Planning is a host-side decision: under ``jit`` the arrays are tracers and
  there is nothing to measure — callers inside a trace must plan beforehand
  (or leave the plan on structural "auto").
  """
  leaves = jax.tree_util.tree_leaves(graph)
  if any(isinstance(x, jax.core.Tracer) for x in leaves):
    raise TypeError(
        "compute_stats/Planner.plan need a concrete graph (host-side); "
        "inside jit, pass a precomputed Plan instead")
  if isinstance(graph, graphlib.DenseGraph):
    struct = np.asarray(graph.struct)
    in_deg = struct.sum(axis=1)
    nnz = int(in_deg.sum())
    mean, mx, cv, hub = _degree_stats(in_deg)
    return GraphStats("dense", graph.n, nnz, mean, mx, cv, hub,
                      nnz / max(graph.n * graph.n, 1))
  if isinstance(graph, graphlib.CooGraph):
    emask = np.asarray(graph.emask)
    in_deg = np.asarray(graph.in_deg)
    nnz = int(emask.sum())
    mean, mx, cv, hub = _degree_stats(in_deg)
    return GraphStats("coo", graph.n, nnz, mean, mx, cv, hub,
                      nnz / max(graph.n * graph.n, 1))
  if isinstance(graph, graphlib.EllGraph):
    mask = np.asarray(graph.mask)
    nnz = int(mask.sum())
    # A vertex's in-degree sums its home row and its split rows.
    row_of = np.asarray(graph.row_of)
    real = row_of < graph.n
    in_deg = np.bincount(row_of[real], weights=mask.sum(axis=0)[real],
                         minlength=graph.n)
    mean, mx, cv, hub = _degree_stats(in_deg)
    return GraphStats(
        "ell", graph.n, nnz, nnz / max(graph.n, 1), mx, cv, hub,
        nnz / max(graph.n * graph.n, 1), ell_width=graph.width,
        ell_efficiency=nnz / max(mask.size, 1))
  raise TypeError(f"unknown graph container {type(graph)}")


class PlanCache:
  """Thread-safe memo of autotuned plans, keyed by graph fingerprint
  (+ program name, query width).  Counts hits/misses for tests/metrics."""

  def __init__(self):
    self._store: Dict[Hashable, Plan] = {}
    self._lock = threading.Lock()
    self.hits = 0
    self.misses = 0

  def get(self, key: Hashable) -> Optional[Plan]:
    with self._lock:
      if key in self._store:
        self.hits += 1
        return self._store[key]
      self.misses += 1
      return None

  def put(self, key: Hashable, plan: Plan) -> None:
    with self._lock:
      self._store[key] = plan

  def __len__(self) -> int:
    with self._lock:
      return len(self._store)

  def __contains__(self, key: Hashable) -> bool:
    with self._lock:
      return key in self._store


@dataclasses.dataclass
class Planner:
  """Picks execution plans from graph statistics (or by measurement).

  Attributes:
    skew_threshold: hub ratio (max/mean in-degree) above which the
      partitioned-COO backend's balanced edge tiles pay off.
    tile_edges: target edges per tile for coo_tiled.
    max_tiles: edge-tile cap.
    ell_efficiency_floor: minimum ELL slot fill for the Pallas kernel to
      beat the jnp ELL path (below it the kernel mostly reduces padding).
    cache: memo for :meth:`autotune` winners (fingerprint-keyed).
    failures: ``(plan, repr(exception))`` of every autotune candidate that
      failed to compile or run (each also raises a RuntimeWarning).
  """

  skew_threshold: float = 4.0
  tile_edges: int = 4096
  max_tiles: int = 64
  ell_efficiency_floor: float = 0.25
  cache: PlanCache = dataclasses.field(default_factory=PlanCache)
  failures: List[Tuple[Plan, str]] = dataclasses.field(default_factory=list)

  # -- heuristic planning ----------------------------------------------------

  def stats(self, graph) -> GraphStats:
    return compute_stats(graph)

  def _coo_tiles(self, stats: GraphStats) -> int:
    return max(2, min(self.max_tiles, -(-stats.nnz // self.tile_edges)))

  def plan(self, graph, program: Optional[GraphProgram] = None,
           q: int = 1) -> Plan:
    """Heuristic plan for running ``program`` (Q-wide) on ``graph``.

    See the module docstring for the decision table.  ``program=None``
    plans conservatively (no kernel/tiling fast paths assumed).
    """
    stats = self.stats(graph)
    if stats.container == "dense":
      return Plan(backend="dense")
    if stats.container == "ell":
      if (kernel_takes(program)
          and stats.ell_efficiency >= self.ell_efficiency_floor):
        return Plan(backend="pallas")
      return Plan(backend="ell")
    # COO: skewed degree distributions lose load balance in one monolithic
    # segment reduce; equal-size edge tiles restore it (paper §4.3).
    fast = program is not None and program.monoid is not None
    if fast and stats.hub_ratio >= self.skew_threshold:
      return Plan(backend="coo_tiled", num_tiles=self._coo_tiles(stats))
    return Plan(backend="coo")

  def candidates(self, graph, program: Optional[GraphProgram] = None,
                 q: int = 1) -> List[Plan]:
    """Candidate plans worth timing for this (graph, program, Q)."""
    stats = self.stats(graph)
    if stats.container == "dense":
      return [Plan(backend="dense")]
    if stats.container == "ell":
      out = [Plan(backend="ell")]
      if kernel_takes(program):
        out.append(Plan(backend="pallas"))
        n_pad = graph.n_pad
        for br in (128, 512):
          if n_pad % br == 0 and n_pad > br:
            out.append(Plan(backend="pallas", block_rows=br))
        if q > 1:
          for bq in (8, 32, 128):
            if q % bq == 0 and q >= bq:
              out.append(Plan(backend="pallas", block_queries=bq))
      return out
    out = [Plan(backend="coo")]
    if program is None or program.monoid is not None:
      t = self._coo_tiles(stats)
      for nt in sorted({t, max(2, t // 4), min(self.max_tiles, t * 4)}):
        out.append(Plan(backend="coo_tiled", num_tiles=nt))
    return out

  # -- measurement-based planning --------------------------------------------

  def autotune(self, graph, program: GraphProgram, init_prop: Any,
               init_active, *, num_iters: int = 2,
               candidates: Optional[Sequence[Plan]] = None,
               repeats: int = 3,
               timer: Callable[[], float] = time.perf_counter) -> Plan:
    """Time candidate plans on a real (short) run; memoize the winner.

    ``init_prop``/``init_active`` seed the measured supersteps — pass the
    same shapes the production workload uses (``bool[n]`` single-query or
    ``bool[n, Q]`` batched; the engine entry point is picked to match).
    Winners are memoized in :attr:`cache` keyed by ``(graph fingerprint,
    program name, Q)``, so identical graph snapshots (content hash, not
    object identity) re-plan for free.
    """
    from repro.service.cache import graph_fingerprint  # lazy: layering
    batched = jnp.ndim(init_active) == 2
    q = int(init_active.shape[1]) if batched else 1
    key = (graph_fingerprint(graph), program.name, q)
    hit = self.cache.get(key)
    if hit is not None:
      return hit

    from repro.core import engine  # lazy: engine imports this package
    cands = list(candidates) if candidates is not None else self.candidates(
        graph, program, q)

    # The graph and initial state are jit arguments, not baked-in constants.
    def runner(g, prop, active, plan: Plan):
      if batched:
        return engine.run_batched(g, program, prop, active,
                                  max_iters=num_iters, backend=plan)
      return engine.run_fixed_iters(g, program, prop, active, num_iters,
                                    backend=plan)

    run_jit = jax.jit(runner, static_argnames="plan")
    best_plan, best_t = None, float("inf")
    for plan in cands:
      def fn():
        return run_jit(graph, init_prop, init_active, plan=plan)
      try:
        jax.block_until_ready(fn())  # compile + warm
        times = []
        for _ in range(repeats):
          t0 = timer()
          jax.block_until_ready(fn())
          times.append(timer() - t0)
        t = float(np.median(times))
      except Exception as e:  # the candidate loses, but never silently
        self.failures.append((plan, repr(e)))
        warnings.warn(f"autotune candidate {plan} failed: {e!r}",
                      RuntimeWarning, stacklevel=2)
        continue
      if t < best_t:
        best_plan, best_t = plan, t
    if best_plan is None:
      best_plan = self.plan(graph, program, q)
    self.cache.put(key, best_plan)
    return best_plan
