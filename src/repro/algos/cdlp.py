"""Community detection by label propagation (LDBC Graphalytics CDLP).

Every vertex starts with its own id as its label.  In each of a fixed number
of synchronous iterations, a vertex takes the label that occurs most often
among its in-neighbours' labels, ties to the smallest; its own label does
not count, and a vertex with no in-neighbour keeps its label.  On a
symmetric graph the in-neighbours are the neighbours, as Graphalytics
counts them on an undirected one.

Message = label; PROCESS = pass it through; REDUCE = ``mode``, which is no
monoid, so only the COO backend runs it (:func:`repro.core.spmv.segment_mode`);
APPLY = take the mode, on the vertices that received a label (the engine
keeps the rest).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.backends.plan import AUTO_PLAN, PlanLike
from repro.core.engine import run_fixed_iters
from repro.core.vertex_program import GraphProgram

Array = jax.Array


def cdlp_program() -> GraphProgram:
  return GraphProgram(
      process_message=lambda m, e, d: m,
      reduce_kind="mode",
      process_reads_dst=False,
      name="cdlp")


def cdlp(graph, num_iters: int, backend: PlanLike = AUTO_PLAN) -> Array:
  """Returns the int32 labels [n] after ``num_iters`` iterations."""
  return _cdlp_jit(graph, num_iters=num_iters, backend=backend)


@functools.partial(jax.jit, static_argnames=("num_iters", "backend"))
def _cdlp_jit(graph, *, num_iters, backend):
  labels = jnp.arange(graph.n, dtype=jnp.int32)
  active = jnp.ones((graph.n,), bool)
  state = run_fixed_iters(graph, cdlp_program(), labels, active, num_iters,
                          backend=backend, keep_all_active=True)
  return state.prop
