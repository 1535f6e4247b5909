"""Distributed generalized SpMV via ``shard_map`` — GraphMat beyond one node.

The paper partitions the matrix 1-D by rows with "many more partitions than
threads" for load balance and relies on a shared-L3 message vector.  The
TPU-mesh analogue:

* **2-D block partitioning** (CombBLAS-style layout, GraphMat-style ops):
  the adjacency is cut into an ``R × C`` grid of edge blocks.  Mesh axis
  "data" (optionally ("pod","data")) carries row blocks, "model" carries
  column blocks.
* The message vector is sharded by *column* block (``P(col)``) — each device
  holds exactly the slice of ``x`` its block needs.  Between supersteps the
  property vector lives row-sharded (``P(row)``); jit inserts the transpose
  resharding automatically (the collective analogue of the paper's shared-
  memory reads).
* Partial outputs are combined along "model" with a **semiring-aware
  reduction**: ``psum``/``pmin``/``pmax`` fast-paths, all-gather + log-fold
  for generic monoids.
* Load balance: blocks are padded to the global max block population — the
  static-shape analogue of over-partitioning; the degree-randomizing vertex
  shuffle in ``repro.graphs.partition`` keeps the max/mean ratio near 1.

Multi-pod: row blocks extend over ("pod","data"), so cross-pod traffic is
zero during the SpMV itself (rows are disjoint) and the only inter-device
collective is the column reduce along "model" (intra-pod ICI).  The
superstep-boundary reshard crosses pods once per iteration.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import graph as graphlib
from repro.core import spmv as spmv_lib
from repro.core.backends.plan import AUTO_PLAN, PlanLike, as_plan
from repro.core.vertex_program import GraphProgram

Array = jax.Array
PyTree = Any


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class DistGraph:
  """``R × C`` block-partitioned edge list with static per-block capacity.

  Block ``(i, j)`` holds edges whose destination falls in row range i and
  source in column range j, with *local* indices.  All blocks are padded to
  the same capacity (static shapes; the local mask annihilates padding).
  """

  n: int          # static: true vertex count
  n_pad: int      # static: padded vertex count (divisible by R and C)
  R: int          # static: row blocks
  C: int          # static: col blocks
  src: Array      # int32[R, C, Eb] local col index within block (0..n_pad/C)
  dst: Array      # int32[R, C, Eb] local row index within block, sorted
  w: Array        # [R, C, Eb]
  emask: Array    # bool[R, C, Eb]

  def tree_flatten(self):
    return ((self.src, self.dst, self.w, self.emask),
            (self.n, self.n_pad, self.R, self.C))

  @classmethod
  def tree_unflatten(cls, aux, children):
    return cls(*aux, *children)

  @property
  def rows_per_block(self) -> int:
    return self.n_pad // self.R

  @property
  def cols_per_block(self) -> int:
    return self.n_pad // self.C


def partition_2d(src, dst, w=None, *, n: int, R: int, C: int,
                 edge_dtype=jnp.float32,
                 mesh: Optional[Mesh] = None) -> DistGraph:
  """Host-side 2-D partitioner (numpy).

  With ``mesh``, block ``(i, j)`` is placed straight onto its device in the
  runners' default layout (``P("data", "model")`` on the two block dims), so
  no device ever holds the whole graph; without it the blocks land on the
  default device.
  """
  dt = np.dtype(edge_dtype)
  src, dst, w = graphlib._as_np_edges(src, dst, w, n, dt)
  n_pad = int(np.ceil(n / (R * C))) * (R * C)  # divisible by both R and C
  nr, nc = n_pad // R, n_pad // C
  bi = dst // nr          # row block
  bj = src // nc          # col block
  ldst = dst % nr
  lsrc = src % nc
  # Sort by (block_i, block_j, local dst) so each block is dst-sorted.
  order = np.lexsort((ldst, bj, bi))
  bi, bj, ldst, lsrc, w = bi[order], bj[order], ldst[order], lsrc[order], w[order]
  # Position of each edge within its block.
  flat = bi * C + bj
  cap = max(int(np.bincount(flat, minlength=R * C).max()), 1)
  bsrc = np.zeros((R, C, cap), np.int32)
  bdst = np.full((R, C, cap), max(nr - 1, 0), np.int32)  # keep dst sorted-ish
  bw = np.zeros((R, C, cap), dt)
  bmask = np.zeros((R, C, cap), bool)
  # edges already sorted by (bi,bj); position = index - first index of block
  first = np.searchsorted(flat, flat)
  pos = np.arange(flat.shape[0]) - first
  bsrc[bi, bj, pos] = lsrc
  bdst[bi, bj, pos] = ldst
  bw[bi, bj, pos] = w
  bmask[bi, bj, pos] = True
  if mesh is None:
    put = jnp.asarray
  else:
    put = partial(jax.device_put,
                  device=NamedSharding(mesh, P("data", "model")))
  return DistGraph(n=n, n_pad=n_pad, R=R, C=C, src=put(bsrc), dst=put(bdst),
                   w=put(bw), emask=put(bmask))


def _refuse_mode(program: GraphProgram) -> None:
  """The column blocks' partial results can only be combined by a monoid."""
  if program.reduce_kind == "mode":
    raise ValueError(f"program {program.name!r}: the 2-D runners cannot "
                     "combine a mode reduce across column blocks")


def _semiring_axis_reduce(y: PyTree, recv: Array, axis_name: str,
                          program: GraphProgram) -> Tuple[PyTree, Array]:
  kind = program.reduce_kind
  if kind == "add":
    y = jax.tree_util.tree_map(partial(jax.lax.psum, axis_name=axis_name), y)
  elif kind == "min":
    y = jax.tree_util.tree_map(partial(jax.lax.pmin, axis_name=axis_name), y)
  elif kind == "max":
    y = jax.tree_util.tree_map(partial(jax.lax.pmax, axis_name=axis_name), y)
  elif kind in ("any", "all"):
    red = jax.lax.pmax if kind == "any" else jax.lax.pmin
    y = jax.tree_util.tree_map(
        lambda x: red(x.astype(jnp.int8), axis_name=axis_name).astype(x.dtype),
        y)
  else:  # generic monoid: all-gather along the axis and fold locally.
    red = program.reduce_fn()
    gathered = jax.tree_util.tree_map(
        lambda x: jax.lax.all_gather(x, axis_name=axis_name, axis=0), y)
    size = jax.tree_util.tree_leaves(gathered)[0].shape[0]
    acc = jax.tree_util.tree_map(lambda x: x[0], gathered)
    for k in range(1, size):
      acc = red(acc, jax.tree_util.tree_map(lambda x: x[k], gathered))
    y = acc
  recv = jax.lax.pmax(recv.astype(jnp.int8), axis_name=axis_name) > 0
  return y, recv


def spmv_2d(g: DistGraph, msg: PyTree, active: Array, dst_prop: PyTree,
            program: GraphProgram, mesh: Mesh,
            row_axes: Sequence[str] = ("data",),
            col_axis: str = "model",
            backend: PlanLike = AUTO_PLAN) -> Tuple[PyTree, Array]:
  """Distributed generalized SpMV over a 2-D (or 3-D w/ pods) mesh.

  Shardings (global view):
    * graph blocks: ``P(row_axes, col_axis)`` on the two leading dims,
    * ``msg``/``active``: ``P(col_axis)`` (column-sharded sources),
    * ``dst_prop`` and outputs: ``P(row_axes)`` (row-sharded destinations).

  ``backend`` plans the *per-device block* SpMV.  Blocks are COO, so valid
  plans are ``coo`` (default under auto) and ``coo_tiled`` — the latter
  nests the paper's partitions-≫-threads edge tiling *inside* each device
  block on top of the 2-D mesh partitioning.
  """
  row = tuple(row_axes)
  rows_spec = row if len(row) > 1 else row[0]
  nr = g.rows_per_block
  plan = as_plan(backend)

  def local(bsrc, bdst, bw, bemask, msg_blk, act_blk, prop_blk):
    # shard_map hands us [1, 1, Eb] block slices — drop the unit block dims.
    bsrc, bdst, bw, bemask = (
        x.reshape(x.shape[2:]) for x in (bsrc, bdst, bw, bemask))
    local_g = graphlib.CooGraph(
        n=nr, src=bsrc, dst=bdst, w=bw, emask=bemask,
        out_deg=jnp.zeros((nr,), jnp.int32),
        in_deg=jnp.zeros((nr,), jnp.int32))
    y_part, recv_part = spmv_lib.spmv(
        local_g, msg_blk, act_blk, prop_blk, program, backend=plan)
    return _semiring_axis_reduce(y_part, recv_part, col_axis, program)

  f = jax.shard_map(
      local, mesh=mesh,
      in_specs=(P(rows_spec, col_axis), P(rows_spec, col_axis),
                P(rows_spec, col_axis), P(rows_spec, col_axis),
                P(col_axis), P(col_axis), P(rows_spec)),
      out_specs=(P(rows_spec), P(rows_spec)),
      check_vma=False)
  return f(g.src, g.dst, g.w, g.emask, msg, active, dst_prop)


def pad_vertex_tree(tree: PyTree, n: int, n_pad: int, fill=0) -> PyTree:
  """Pad leading vertex axis from n to n_pad with ``fill``."""
  if n_pad == n:
    return tree
  def padleaf(x):
    pad_width = [(0, n_pad - n)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad_width, constant_values=fill)
  return jax.tree_util.tree_map(padleaf, tree)


def run_graph_program_2d(
    g: DistGraph, program: GraphProgram, init_prop: PyTree,
    init_active: Array, mesh: Mesh, *,
    max_iters: int = 0x7FFFFFF0,
    row_axes: Sequence[str] = ("data",), col_axis: str = "model",
    backend: PlanLike = AUTO_PLAN):
  """Distributed Algorithm 2: the full superstep loop under one jit.

  ``init_prop``/``init_active`` must already be padded to ``g.n_pad``.
  Returns the final (prop, active, iteration, num_active) like the local
  engine.
  """
  from repro.core.engine import EngineState  # circular-import dodge

  _refuse_mode(program)
  row = tuple(row_axes)
  rows_spec = row if len(row) > 1 else row[0]
  plan = as_plan(backend)
  prop_sharding = NamedSharding(mesh, P(rows_spec))
  col_sharding = NamedSharding(mesh, P(col_axis))

  def constrain(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.lax.with_sharding_constraint(x, sharding), tree)

  def superstep(g: DistGraph, state: EngineState) -> EngineState:
    msg = jax.vmap(program.send_message)(state.prop)
    # Reshard sources column-wise (the superstep-boundary transpose).
    msg = constrain(msg, col_sharding)
    act = jax.lax.with_sharding_constraint(state.active, col_sharding)
    y, recv = spmv_2d(g, msg, act, state.prop, program, mesh,
                      row_axes=row, col_axis=col_axis, backend=plan)
    new_prop = jax.vmap(program.apply)(y, state.prop)
    new_prop = spmv_lib._tree_where(recv, new_prop, state.prop)
    new_prop = constrain(new_prop, prop_sharding)
    changed = jnp.logical_and(recv, program.activate(state.prop, new_prop))
    return EngineState(new_prop, changed, state.iteration + 1,
                       jnp.sum(changed.astype(jnp.int32)))

  # The graph is a jit argument, so its blocks stay where partition_2d put
  # them instead of being baked into the program as constants.
  @jax.jit
  def loop(g, prop0, active0):
    state = EngineState(prop0, active0, jnp.int32(0),
                        jnp.sum(active0.astype(jnp.int32)))
    return jax.lax.while_loop(
        lambda s: jnp.logical_and(s.iteration < max_iters, s.num_active > 0),
        partial(superstep, g), state)

  return loop(g, init_prop, init_active)


def run_graph_program_2d_batched(
    g: DistGraph, program: GraphProgram, init_prop: PyTree,
    init_active: Array, mesh: Mesh, *,
    max_iters: int = 0x7FFFFFF0,
    row_axes: Sequence[str] = ("data",), col_axis: str = "model",
    backend: PlanLike = AUTO_PLAN):
  """Distributed batched multi-query loop (SpMM over the 2-D mesh).

  The query axis (dim 1 of every leaf, ``[n_pad, Q, ...]``) is carried
  *unsharded* through the 2-D block partitioning: ``P(col)``/``P(row)``
  constrain only the vertex axis, so each device's local SpMV simply grows a
  payload axis — the distributed analogue of the local batched engine.

  ``init_prop``/``init_active`` must already be padded to ``g.n_pad``.
  Requires a batched-ready program (``inert_message`` set, per-lane
  ``activate``).  Returns the final :class:`BatchedEngineState`.
  """
  from repro.core.engine import BatchedEngineState, init_batched_state

  _refuse_mode(program)
  row = tuple(row_axes)
  rows_spec = row if len(row) > 1 else row[0]
  plan = as_plan(backend)
  prop_sharding = NamedSharding(mesh, P(rows_spec))
  col_sharding = NamedSharding(mesh, P(col_axis))

  def constrain(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.lax.with_sharding_constraint(x, sharding), tree)

  def superstep(g: DistGraph, state: BatchedEngineState
                ) -> BatchedEngineState:
    live = jnp.logical_not(state.done)
    msg = jax.vmap(program.send_message)(state.prop)
    lane_mask = jnp.logical_and(state.active, live[None, :])
    msg = spmv_lib.mask_inert(msg, lane_mask, program)
    # Reshard sources column-wise (P only constrains the vertex axis; the
    # query axis stays replicated along "model").
    msg = constrain(msg, col_sharding)
    vert_active = jax.lax.with_sharding_constraint(
        jnp.any(lane_mask, axis=1), col_sharding)
    y, recv = spmv_2d(g, msg, vert_active, state.prop, program, mesh,
                      row_axes=row, col_axis=col_axis, backend=plan)
    new_prop = jax.vmap(program.apply)(y, state.prop)
    if program.needs_recv:
      new_prop = spmv_lib._tree_where(recv, new_prop, state.prop)
      changed = jnp.logical_and(recv[:, None],
                                program.activate(state.prop, new_prop))
    else:
      changed = program.activate(state.prop, new_prop)
    new_prop = constrain(new_prop, prop_sharding)
    changed = jnp.logical_and(changed, live[None, :])
    num_active = jnp.sum(changed.astype(jnp.int32), axis=0)
    return BatchedEngineState(
        prop=new_prop, active=changed, iteration=state.iteration + 1,
        done=jnp.logical_or(state.done, num_active == 0),
        num_active=num_active,
        iters=state.iters + live.astype(jnp.int32))

  @jax.jit
  def loop(g, prop0, active0):  # the graph is an argument, as above
    state = init_batched_state(prop0, active0)
    return jax.lax.while_loop(
        lambda s: jnp.logical_and(s.iteration < max_iters,
                                  jnp.logical_not(jnp.all(s.done))),
        partial(superstep, g), state)

  return loop(g, init_prop, init_active)
