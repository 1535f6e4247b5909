"""SSSP through the program's public entries: ``repro.algos.sssp.sssp`` for
batch runs, ``SsspFamily`` for served queries.

A run traverses the out-arcs of every vertex it reaches, once.  The
engine's superstep count is not exposed by the entry, so none is reported.
"""

import numpy as np

from repro.algos.sssp import sssp
from repro.service import QuerySpec, SsspFamily


class Batch:
  """Runs from the traffic's ``roots`` distinct roots of nonzero degree,
  taken in an order drawn from the run's seed (Graph500's search keys).
  The roots are drawn from the seed of the graph's edges, so where the
  configuration fixes the graph every run seed gets the same roots under
  its own labels, and the same work."""

  def __init__(self, ctx):
    arcs = ctx.arcs
    self.n = arcs.n
    self.plan = ctx.plan
    self.out_deg = np.bincount(arcs.src, minlength=self.n)
    cand = np.flatnonzero(self.out_deg[arcs.perm] > 0)   # unpermuted labels
    roots = np.random.default_rng([arcs.graph_seed, 1]).choice(
        cand, ctx.traffic["roots"], replace=False)
    order = np.random.default_rng([ctx.seed, 1]).permutation(roots.size)
    self.keys = [int(arcs.perm[r]) for r in roots[order]]
    # Warm-up from a vertex of degree 0 where there is one: the same
    # program, one superstep long.
    alone = np.flatnonzero(self.out_deg == 0)
    self.warm_key = int(alone[0]) if alone.size else self.keys[-1]

  def call(self, graph, key):
    return sssp(graph, key, self.n, backend=self.plan)

  def arcs_traversed(self, key, answer) -> int:
    return int(self.out_deg[np.isfinite(answer)].sum())

  def supersteps(self, key, answer):
    return None


def family(ctx):
  return SsspFamily(ctx.arcs.n)


def query(source: int) -> QuerySpec:
  return QuerySpec("sssp", source)
