"""CDLP through the program's public entry, ``repro.algos.cdlp``.

Every run is the same call: the configuration's iterations over the whole
graph, every vertex active.  Each iteration traverses every arc once.
"""

from repro.algos.cdlp import cdlp


class Batch:
  """What the batch driver calls, and what it counts, for CDLP."""

  def __init__(self, ctx):
    self.iterations = int(ctx.config["cdlp"]["iterations"])
    self.plan = ctx.plan
    self.num_arcs = ctx.arcs.num_arcs
    self.keys = [None]        # one input: every run is the same
    self.warm_key = None

  def call(self, graph, key):
    return cdlp(graph, self.iterations, backend=self.plan)

  def arcs_traversed(self, key, answer) -> int:
    return self.num_arcs * self.iterations

  def supersteps(self, key, answer) -> int:
    return self.iterations
