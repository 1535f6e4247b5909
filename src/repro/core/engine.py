"""The GraphMat superstep engine (Algorithm 2 of the paper).

Runs a :class:`GraphProgram` to convergence under the bulk-synchronous
model: SEND_MESSAGE over the active set → generalized SpMV → APPLY → next
active set = vertices whose property changed.  Terminates when the frontier
empties or ``max_iters`` supersteps have run.

The whole loop is a single ``jax.lax.while_loop`` under ``jit``: the frontier
is the paper's bitvector (a dense ``bool[n]`` mask) and properties live in
fixed-shape pytrees, so there is no retracing across supersteps.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import spmv as spmv_lib
from repro.core.backends.plan import AUTO_PLAN, Plan, PlanLike, as_plan
from repro.core.vertex_program import GraphProgram

Array = jax.Array
PyTree = Any


class EngineState(NamedTuple):
  prop: PyTree           # vertex properties, leaves [n, ...]
  active: Array          # bool[n] frontier (the paper's bitvector)
  iteration: Array       # int32 scalar
  num_active: Array      # int32 scalar (for stats / convergence)


def _send_spmv_apply(graph, program: GraphProgram, prop: PyTree,
                     active: Array, plan: Plan, live: Optional[Array] = None
                     ) -> Tuple[PyTree, Array]:
  """SEND, the generalized SpMV and APPLY of one superstep, for either state
  shape: returns the new properties and the raw next frontier.

  ``live`` (the batched body's ``bool[Q]`` of unretired columns) folds the
  per-query frontier ``active[n, Q]`` into the payload: inactive lanes, and
  whole retired columns, send the inert message, and the SpMV's bitvector is
  the OR over the lanes.
  """
  # SEND_MESSAGE for active vertices (vectorized; inactive lanes annihilated
  # inside the SpMV by the active mask).
  with jax.named_scope(spmv_lib.SCOPE_SEND):
    msg = jax.vmap(program.send_message)(prop)
    if live is not None:
      lane_mask = jnp.logical_and(active, live[None, :])
      msg = spmv_lib.mask_inert(msg, lane_mask, program)
      active = jnp.any(lane_mask, axis=1)
  # Generalized SpMV: PROCESS_MESSAGE ⊗ / REDUCE ⊕.
  y, recv = spmv_lib.spmv(graph, msg, active, prop, program, backend=plan,
                          with_recv=program.needs_recv)
  # APPLY for vertices that received a message.  Monotone programs
  # (needs_recv=False) apply unconditionally: APPLY(identity, old) == old,
  # so the receive mask and its E-sized scatter are skipped entirely.
  with jax.named_scope(spmv_lib.SCOPE_APPLY):
    new_prop = jax.vmap(program.apply)(y, prop)
    if not program.needs_recv:
      return new_prop, program.activate(prop, new_prop)
    # recv is per vertex (any query delivered); per-lane correctness relies
    # on the inert-message contract: untouched lanes see an identity-reduced
    # input and APPLY must leave them unchanged (see GraphProgram docs).
    new_prop = spmv_lib._tree_where(recv, new_prop, prop)
    if live is not None:
      recv = recv[:, None]
    return new_prop, jnp.logical_and(recv, program.activate(prop, new_prop))


def _superstep(graph, program: GraphProgram, state: EngineState,
               plan: Plan) -> EngineState:
  new_prop, changed = _send_spmv_apply(graph, program, state.prop,
                                       state.active, plan)
  with jax.named_scope(spmv_lib.SCOPE_APPLY):
    return EngineState(
        prop=new_prop,
        active=changed,
        iteration=state.iteration + 1,
        num_active=jnp.sum(changed.astype(jnp.int32)),
    )


def run_graph_program(
    graph,
    program: GraphProgram,
    init_prop: PyTree,
    init_active: Array,
    *,
    max_iters: int = 0x7FFFFFF0,
    backend: PlanLike = AUTO_PLAN,
) -> EngineState:
  """Run ``program`` on ``graph`` until convergence (paper's Algorithm 2).

  Args:
    graph: a container some registered backend runs: a DenseGraph,
      CooGraph or EllGraph, or a DistGraph on its mesh
      (:func:`repro.core.distributed.run_graph_program_2d`).
    init_prop: vertex-property pytree, leaves ``[n, ...]``.
    init_active: ``bool[n]`` initial frontier.
    max_iters: superstep cap (-1 semantics of the paper = "huge").
    backend: execution plan — a :class:`repro.core.backends.Plan`, a
      registered backend name (legacy string shim), or None/"auto".

  Returns the final :class:`EngineState`.
  """
  plan = as_plan(backend)
  n_active0 = jnp.sum(init_active.astype(jnp.int32))
  state = EngineState(init_prop, init_active, jnp.int32(0), n_active0)

  def cond(s: EngineState):
    return jnp.logical_and(s.iteration < max_iters, s.num_active > 0)

  def body(s: EngineState):
    return _superstep(graph, program, s, plan)

  return jax.lax.while_loop(cond, body, state)


# ---------------------------------------------------------------------------
# Batched multi-query engine (SpMV → SpMM)
# ---------------------------------------------------------------------------
#
# Q independent queries of the *same* vertex program run as one fused loop:
# property/message leaves grow a query axis at dim 1 (``[n, Q, ...]``), the
# frontier becomes ``bool[n, Q]``, and the generalized SpMV becomes a
# generalized SpMM — every gathered edge is reused across all Q lanes, the
# arithmetic-intensity lever of GraphBLAST's SpMV→SpMM widening.
#
# Per-query frontier masking is folded into the payload: lanes inactive in
# query q send ``program.inert_message`` (which the program guarantees cannot
# change any destination), and the backend-level bitvector is the column-OR
# ``any_q active[:, q]``.  No backend changes are needed — the query axis is
# just a trailing payload axis to spmv_{dense,coo,ell,pallas}.
#
# Convergence is tracked per column: ``done[q]`` latches once query q's
# frontier empties, and retired columns are hard-masked out of the frontier
# so they stay inert until the service layer swaps a fresh query into the
# slot (continuous batching).


class BatchedEngineState(NamedTuple):
  prop: PyTree           # vertex properties, leaves [n, Q, ...]
  active: Array          # bool[n, Q] per-query frontier
  iteration: Array       # int32 scalar (global superstep count)
  done: Array            # bool[Q] latched per-column convergence
  num_active: Array      # int32[Q] frontier population per query
  iters: Array           # int32[Q] supersteps each query has been live


def init_batched_state(init_prop: PyTree, init_active: Array
                       ) -> BatchedEngineState:
  """Build the step-0 batched state from ``[n, Q]``-shaped init values."""
  num_active = jnp.sum(init_active.astype(jnp.int32), axis=0)
  q = init_active.shape[1]
  return BatchedEngineState(
      prop=init_prop,
      active=init_active,
      iteration=jnp.int32(0),
      done=num_active == 0,
      num_active=num_active,
      iters=jnp.zeros((q,), jnp.int32),
  )


def _batched_superstep(graph, program: GraphProgram,
                       state: BatchedEngineState,
                       plan: Plan) -> BatchedEngineState:
  with jax.named_scope(spmv_lib.SCOPE_SEND):
    live = jnp.logical_not(state.done)
  new_prop, changed = _send_spmv_apply(graph, program, state.prop,
                                       state.active, plan, live)
  with jax.named_scope(spmv_lib.SCOPE_APPLY):
    changed = jnp.logical_and(changed, live[None, :])   # retired stay dead
    num_active = jnp.sum(changed.astype(jnp.int32), axis=0)
    return BatchedEngineState(
        prop=new_prop,
        active=changed,
        iteration=state.iteration + 1,
        done=jnp.logical_or(state.done, num_active == 0),
        num_active=num_active,
        iters=state.iters + live.astype(jnp.int32),
    )


def run_batched(
    graph,
    program: GraphProgram,
    init_prop: PyTree,
    init_active: Array,
    *,
    max_iters: int = 0x7FFFFFF0,
    backend: PlanLike = AUTO_PLAN,
) -> BatchedEngineState:
  """Run Q batched queries of ``program`` until every column converges.

  Args:
    graph: a container some registered backend runs (see
      :func:`run_graph_program`).
    init_prop: vertex-property pytree, leaves ``[n, Q, ...]``.
    init_active: ``bool[n, Q]`` initial per-query frontiers.
    max_iters: global superstep cap.
    backend: execution plan (Plan | backend-name string | None/"auto").

  The program must be batched-ready: ``inert_message`` set and an
  ``activate`` rule that preserves the query axis (e.g.
  :func:`repro.core.vertex_program.lanewise_activate`).
  """
  plan = as_plan(backend)
  state = init_batched_state(init_prop, init_active)

  def cond(s: BatchedEngineState):
    return jnp.logical_and(s.iteration < max_iters,
                           jnp.logical_not(jnp.all(s.done)))

  def body(s: BatchedEngineState):
    return _batched_superstep(graph, program, s, plan)

  return jax.lax.while_loop(cond, body, state)


def mask_columns(state: BatchedEngineState, slots: Array
                 ) -> BatchedEngineState:
  """Hard-retire the given columns: clear their frontier and latch ``done``.

  The early-retirement primitive for the service layer — deadline expiry,
  cancellation, and shutdown all reduce to "stop this column now".  A masked
  column sends only inert messages from the next superstep on, and lane
  independence of :func:`_batched_superstep` (each query's messages reduce
  only into its own column) guarantees the surviving columns' trajectories
  are bitwise-unchanged.

  Args:
    slots: ``int32[k]`` slot indices to retire.
  """
  slots = jnp.asarray(slots, jnp.int32)
  return BatchedEngineState(
      prop=state.prop,
      active=state.active.at[:, slots].set(False),
      iteration=state.iteration,
      done=state.done.at[slots].set(True),
      num_active=state.num_active.at[slots].set(0),
      iters=state.iters,
  )


def run_batched_rounds(graph, program: GraphProgram,
                       state: BatchedEngineState, num_steps: int,
                       backend: PlanLike = AUTO_PLAN
                       ) -> Tuple[BatchedEngineState, Array]:
  """Advance the batched engine by up to ``num_steps`` supersteps.

  The continuous-batching control point: the service scheduler calls this,
  inspects ``done`` on the host, retires/refills slots, and calls it again —
  unconverged columns keep their state across the host round-trip.

  A step where every column is already done is a no-op (state is carried
  through unchanged) so converged batches don't burn SpMM work while the
  scheduler drains the queue.

  Returns ``(state, trace)`` where ``trace[t] = int32`` total frontier
  population at the *end* of step t (-1 for no-op steps).
  """

  plan = as_plan(backend)

  def body(t, carry):
    s, trace = carry
    any_live = jnp.logical_not(jnp.all(s.done))
    s2 = _batched_superstep(graph, program, s, plan)
    with jax.named_scope(spmv_lib.SCOPE_APPLY):   # the step's state update
      s = jax.tree_util.tree_map(
          lambda a, b: jnp.where(any_live, a, b), s2, s)
      trace = trace.at[t].set(
          jnp.where(any_live, jnp.sum(s.num_active), jnp.int32(-1)))
    return s, trace

  trace0 = jnp.full((num_steps,), -1, jnp.int32)
  return jax.lax.fori_loop(0, num_steps, body, (state, trace0))


def run_fixed_iters(graph, program: GraphProgram, init_prop: PyTree,
                    init_active: Array, num_iters: int,
                    backend: PlanLike = AUTO_PLAN,
                    keep_all_active: bool = True) -> EngineState:
  """Fixed-iteration variant (PageRank/CF style) via ``fori_loop``.

  ``keep_all_active`` re-arms the full frontier each superstep — the paper
  runs PR/CF as fixed sweeps where every vertex broadcasts every iteration.
  """
  plan = as_plan(backend)
  state = EngineState(init_prop, init_active, jnp.int32(0),
                      jnp.sum(init_active.astype(jnp.int32)))

  def body(_, s):
    s = _superstep(graph, program, s, plan)
    if keep_all_active:
      with jax.named_scope(spmv_lib.SCOPE_APPLY):
        s = s._replace(active=jnp.ones_like(s.active),
                       num_active=jnp.int32(s.active.shape[0]))
    return s

  return jax.lax.fori_loop(0, num_iters, body, state)
