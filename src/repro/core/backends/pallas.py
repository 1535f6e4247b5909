"""Pallas backend: the fused blocked-ELL TPU kernel (paper's hot loop).

The plan's ``block_rows`` / ``block_slots`` / ``block_queries`` override the
kernel's divisor heuristics — the tuning surface
:meth:`repro.core.backends.planner.Planner.autotune` sweeps.
"""

from __future__ import annotations

from repro.core import graph as graphlib
from repro.core import spmv as spmv_lib
from repro.core.backends import base


class PallasEllBackend(base.Backend):
  name = "pallas"
  container = "ell"
  priority = 90  # preferred over jnp-ELL when the program shape qualifies

  def supports(self, graph, msg, dst_prop, program):
    # Container-level only: an *explicit* pallas plan on an EllGraph always
    # routes here (shape restrictions are asserted in kernels.ops, matching
    # the legacy backend="pallas" error behavior), but for a mode program,
    # which no ELL path runs.
    return (isinstance(graph, graphlib.EllGraph)
            and program.reduce_kind != "mode")

  def eligible(self, graph, msg, dst_prop, program):
    return (isinstance(graph, graphlib.EllGraph)
            and spmv_lib._pallas_eligible(graph, msg, dst_prop, program))

  def execute(self, graph, msg, active, dst_prop, program, plan, with_recv):
    from repro.kernels import ops as kops  # local import: optional dep
    y, recv = kops.spmv_ell_pallas(graph, msg, active, dst_prop, program,
                                   **plan.kernel_kwargs())
    return y, (recv if with_recv else None)


base.register(PallasEllBackend())
