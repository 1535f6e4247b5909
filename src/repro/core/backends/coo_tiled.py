"""Partitioned-COO backend: equal-size edge tiles, cache-blocked scatters.

The paper's "many more partitions than threads" load-balancing trick,
expressed as static-shape tiling of the dst-sorted edge array (see
:func:`repro.core.spmv.spmv_coo_tiled`).  Planner-selected for skewed-degree
graphs (or explicit via ``Plan(backend="coo_tiled", num_tiles=...)``);
structural auto keeps picking the untiled COO backend, so legacy ``"auto"``
behavior is unchanged.
"""

from __future__ import annotations

from repro.core import graph as graphlib
from repro.core import spmv as spmv_lib
from repro.core.backends import base


class TiledCooBackend(base.Backend):
  name = "coo_tiled"
  container = "coo"
  priority = 70

  def supports(self, graph, msg, dst_prop, program):
    return isinstance(graph, graphlib.CooGraph) and program.monoid is not None

  def eligible(self, graph, msg, dst_prop, program):
    # Profitability (tile count vs. skew) is data the tracer can't see:
    # only the host-side Planner or an explicit plan selects this backend.
    return False

  def execute(self, graph, msg, active, dst_prop, program, plan, with_recv):
    return spmv_lib.spmv_coo_tiled(graph, msg, active, dst_prop, program,
                                   num_tiles=plan.num_tiles,
                                   with_recv=with_recv)


base.register(TiledCooBackend())
