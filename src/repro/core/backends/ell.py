"""ELL backend: degree-sorted slot-major packed rows, reduce over slots (+ split-row fold)."""

from __future__ import annotations

from repro.core import graph as graphlib
from repro.core import spmv as spmv_lib
from repro.core.backends import base


class EllBackend(base.Backend):
  name = "ell"
  container = "ell"
  priority = 80  # EllGraph fallback when the Pallas kernel is ineligible

  def supports(self, graph, msg, dst_prop, program):
    return (isinstance(graph, graphlib.EllGraph)
            and program.reduce_kind != "mode")

  def execute(self, graph, msg, active, dst_prop, program, plan, with_recv):
    return spmv_lib.spmv_ell(graph, msg, active, dst_prop, program,
                             with_recv=with_recv)


base.register(EllBackend())
