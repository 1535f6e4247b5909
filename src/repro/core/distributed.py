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
  reduction**: the monoid's collective from
  :data:`repro.core.semiring.MONOIDS` (``psum``/``pmin``/``pmax``), an
  all-gather + fold for generic monoids.
* The runners are the engine's own loops and superstep; a registered
  backend for a DistGraph on its mesh puts :func:`spmv_2d` in the SpMV's
  place.
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
from functools import partial
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import backends
from repro.core import engine
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


def _semiring_axis_reduce(y: PyTree, recv: Array, axis_name: str,
                          program: GraphProgram) -> Tuple[PyTree, Array]:
  """Combine the column blocks' partial ``(y, recv)`` along ``axis_name``:
  the monoid's collective, or for a generic monoid an all-gather and a fold."""
  monoid = program.monoid
  if monoid is not None:
    y = jax.tree_util.tree_map(lambda x: monoid.collective(x, axis_name), y)
  else:
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


def _refuse_mode(program: GraphProgram) -> None:
  """The column blocks' partial results can only be combined by a monoid."""
  if program.reduce_kind == "mode":
    raise ValueError(f"program {program.name!r}: the 2-D SpMV cannot "
                     "combine a mode reduce across column blocks")


def spmv_2d(g: DistGraph, msg: PyTree, active: Array, dst_prop: PyTree,
            program: GraphProgram, mesh: Mesh,
            row_axes: Sequence[str] = ("data",),
            col_axis: str = "model",
            backend: PlanLike = AUTO_PLAN) -> Tuple[PyTree, Array]:
  """Distributed generalized SpMV over a 2-D (or 3-D w/ pods) mesh.

  Shardings (global view):
    * graph blocks: ``P(row_axes, col_axis)`` on the two leading dims,
    * ``msg``/``active``: resharded here to ``P(col_axis)`` (column-sharded
      sources; the superstep-boundary transpose),
    * ``dst_prop`` and outputs: ``P(row_axes)`` (row-sharded destinations).

  A leaf's axes past the vertex axis (the batched engine's query axis) are
  not sharded: each device's block SpMV grows a payload axis.

  ``backend`` plans the *per-device block* SpMV.  Blocks are COO, so valid
  plans are ``coo`` (default under auto) and ``coo_tiled`` — the latter
  nests the paper's partitions-≫-threads edge tiling *inside* each device
  block on top of the 2-D mesh partitioning.  The column blocks' partial
  results are combined by a monoid, so a ``mode`` program is refused.
  """
  _refuse_mode(program)
  row = tuple(row_axes)
  rows_spec = row if len(row) > 1 else row[0]
  nr = g.rows_per_block
  plan = as_plan(backend)
  col_sharding = NamedSharding(mesh, P(col_axis))
  msg = jax.tree_util.tree_map(
      lambda x: jax.lax.with_sharding_constraint(x, col_sharding), msg)
  active = jax.lax.with_sharding_constraint(active, col_sharding)

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


@partial(jax.tree_util.register_dataclass, data_fields=["g"],
         meta_fields=["mesh", "row_axes", "col_axis"])
@dataclasses.dataclass(frozen=True)
class _OnMesh:
  """A :class:`DistGraph` with the mesh layout :func:`spmv_2d` runs it on:
  the container through which the engine's superstep reaches the 2-D SpMV."""

  g: DistGraph
  mesh: Mesh
  row_axes: Tuple[str, ...]
  col_axis: str


class Mesh2dBackend(backends.Backend):
  """The 2-D SpMV over a mesh; the plan it is given plans the block SpMV."""

  name = "mesh_2d"
  container = "dist"
  priority = 50    # the only backend that runs the container

  def supports(self, graph, msg, dst_prop, program):
    return isinstance(graph, _OnMesh)

  def execute(self, graph, msg, active, dst_prop, program, plan, with_recv):
    y, recv = spmv_2d(graph.g, msg, active, dst_prop, program, graph.mesh,
                      row_axes=graph.row_axes, col_axis=graph.col_axis,
                      backend=plan)
    return y, (recv if with_recv else None)


backends.register(Mesh2dBackend())


def pad_vertex_tree(tree: PyTree, n: int, n_pad: int, fill=0) -> PyTree:
  """Pad leading vertex axis from n to n_pad with ``fill``."""
  if n_pad == n:
    return tree
  def padleaf(x):
    pad_width = [(0, n_pad - n)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad_width, constant_values=fill)
  return jax.tree_util.tree_map(padleaf, tree)


def _run_on_mesh(run, g: DistGraph, program: GraphProgram, init_prop: PyTree,
                 init_active: Array, mesh: Mesh, max_iters: int,
                 row_axes: Sequence[str], col_axis: str, backend: PlanLike):
  """``run`` (an engine loop) over ``g`` on ``mesh``, under one jit.

  The graph is a jit argument, so its blocks stay where partition_2d put
  them instead of being baked into the program as constants.  A ``mode``
  program is refused before tracing: the batched SEND would first miss its
  inert message.
  """
  _refuse_mode(program)
  plan = as_plan(backend)

  @jax.jit
  def loop(on_mesh, prop0, active0):
    return run(on_mesh, program, prop0, active0, max_iters=max_iters,
               backend=plan)

  return loop(_OnMesh(g, mesh, tuple(row_axes), col_axis), init_prop,
              init_active)


def run_graph_program_2d(
    g: DistGraph, program: GraphProgram, init_prop: PyTree,
    init_active: Array, mesh: Mesh, *,
    max_iters: int = 0x7FFFFFF0,
    row_axes: Sequence[str] = ("data",), col_axis: str = "model",
    backend: PlanLike = AUTO_PLAN):
  """Distributed Algorithm 2: the engine's loop
  (:func:`repro.core.engine.run_graph_program`) with :func:`spmv_2d` as its
  SpMV, under one jit.

  ``init_prop``/``init_active`` must already be padded to ``g.n_pad``.
  ``backend`` plans the block SpMV.  Returns the final
  :class:`~repro.core.engine.EngineState`, as the local engine does.
  """
  return _run_on_mesh(engine.run_graph_program, g, program, init_prop,
                      init_active, mesh, max_iters, row_axes, col_axis,
                      backend)


def run_graph_program_2d_batched(
    g: DistGraph, program: GraphProgram, init_prop: PyTree,
    init_active: Array, mesh: Mesh, *,
    max_iters: int = 0x7FFFFFF0,
    row_axes: Sequence[str] = ("data",), col_axis: str = "model",
    backend: PlanLike = AUTO_PLAN):
  """Distributed batched multi-query loop: the engine's
  :func:`repro.core.engine.run_batched` with :func:`spmv_2d` as its SpMV
  (an SpMM over the 2-D mesh).

  The query axis (dim 1 of every leaf, ``[n_pad, Q, ...]``) is carried
  *unsharded* through the 2-D block partitioning: ``P(col)``/``P(row)``
  constrain only the vertex axis, so each device's local SpMV simply grows a
  payload axis — the distributed analogue of the local batched engine.

  ``init_prop``/``init_active`` must already be padded to ``g.n_pad``.
  Requires a batched-ready program (``inert_message`` set, per-lane
  ``activate``).  Returns the final :class:`BatchedEngineState`.
  """
  return _run_on_mesh(engine.run_batched, g, program, init_prop, init_active,
                      mesh, max_iters, row_axes, col_axis, backend)
