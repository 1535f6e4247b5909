"""PageRank through the program's public entry, ``repro.algos.pagerank``.

Every run is the same call: the configuration's sweeps and ``r`` over the
whole graph, every vertex active.  Each sweep traverses every arc once.
"""

import jax.numpy as jnp
import numpy as np

from repro.algos.pagerank import pagerank


class Batch:
  """What the batch driver calls, and what it counts, for PageRank."""

  def __init__(self, ctx):
    pr = ctx.config["pagerank"]
    self.sweeps = int(pr["sweeps"])
    self.kwargs = {"num_iters": self.sweeps, "r": float(pr["r"]),
                   "backend": ctx.plan}
    deg = np.bincount(ctx.arcs.src, minlength=ctx.arcs.n)
    self.out_deg = jnp.asarray(deg.astype(np.float32))
    self.num_arcs = ctx.arcs.num_arcs
    self.keys = [None]        # one input: every run is the same
    self.warm_key = None

  def call(self, graph, key):
    return pagerank(graph, self.out_deg, **self.kwargs)

  def arcs_traversed(self, key, answer) -> int:
    return self.num_arcs * self.sweeps

  def supersteps(self, key, answer) -> int:
    return self.sweeps
