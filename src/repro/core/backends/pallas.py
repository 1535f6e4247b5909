"""Pallas backend: the fused blocked-ELL TPU kernel (paper's hot loop).

The plan's ``block_rows`` / ``block_slots`` / ``block_queries`` override the
kernel's divisor heuristics — the tuning surface
:meth:`repro.core.backends.planner.Planner.autotune` sweeps.
"""

from __future__ import annotations

from typing import Any, Optional

import jax

from repro.core import graph as graphlib
from repro.core.backends import base
from repro.core.vertex_program import GraphProgram


def kernel_takes(program: Optional[GraphProgram], msg: Any = None,
                 dst_prop: Any = None) -> bool:
  """Whether the Pallas kernel can run ``program``, and given ``msg``, this
  call.

  The program part (all the planner can ask before a payload exists): a
  reduce kind the kernel takes (``Monoid.pallas``) and at most one message
  dim.  The payload part: one message leaf, ``[n]`` scalars or ``[n, Q]``
  lanes of a lanewise program, and where PROCESS reads the destination, one
  property leaf of no higher rank.  Everything else runs on the jnp ELL
  path.
  """
  if program is None or program.monoid is None or not program.monoid.pallas:
    return False
  if program.num_message_dims > 1:
    return False
  if msg is None:
    return True
  leaves = jax.tree_util.tree_leaves(msg)
  if len(leaves) != 1:
    return False
  ndim = leaves[0].ndim
  if ndim > 2 or (ndim == 2 and not program.lanewise):
    return False
  dp_leaves = jax.tree_util.tree_leaves(dst_prop)
  return (not program.process_reads_dst) or (
      len(dp_leaves) == 1 and dp_leaves[0].ndim <= ndim)


class PallasEllBackend(base.Backend):
  name = "pallas"
  container = "ell"
  priority = 90  # preferred over jnp-ELL when the program shape qualifies

  def supports(self, graph, msg, dst_prop, program):
    # Container-level only: an *explicit* pallas plan on an EllGraph always
    # routes here (kernels.ops refuses what ``kernel_takes`` refuses, as the
    # legacy backend="pallas" did), but for a mode program, which no ELL
    # path runs.
    return (isinstance(graph, graphlib.EllGraph)
            and program.reduce_kind != "mode")

  def eligible(self, graph, msg, dst_prop, program):
    return (isinstance(graph, graphlib.EllGraph)
            and kernel_takes(program, msg, dst_prop))

  def execute(self, graph, msg, active, dst_prop, program, plan, with_recv):
    from repro.kernels import ops as kops  # local import: optional dep
    y, recv = kops.spmv_ell_pallas(graph, msg, active, dst_prop, program,
                                   **plan.kernel_kwargs())
    return y, (recv if with_recv else None)


base.register(PallasEllBackend())
