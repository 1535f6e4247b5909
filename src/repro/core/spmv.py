"""Generalized SpMV backends (Algorithm 1 of the paper, TPU-native).

Every backend computes, for each edge ``(u → v)`` with ``active[u]``::

    y[v] = REDUCE(y[v], PROCESS_MESSAGE(msg[u], w_uv, prop[v]))

and a ``recv[v]`` mask marking vertices that received ≥1 message.  Inactive
sources are annihilated by the reduce identity — the dense-value-array +
bitvector sparse-vector representation the paper itself measured to be best
(Section 4.4.2) maps 1:1 onto TPU-friendly masked dense compute.

Backends:
  * ``spmv_dense`` — O(n²) masked oracle for tests.
  * ``spmv_coo``   — gather + segmented reduce over a dst-sorted edge list
                     (scatter fast-paths for add/min/max/any; associative
                     segmented scan for generic monoids; a segmented sort
                     for ``mode``, the one reduce that is no monoid).
  * ``spmv_ell``   — degree-sorted slot-major ELL: gather + reduce over
                     slots — the layout consumed by the Pallas kernel; the
                     split rows of hubs are folded into their owners.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import graph as graphlib
from repro.core.vertex_program import GraphProgram

Array = jax.Array
PyTree = Any

# Named scopes of a superstep's phases, all under one root, ``graphmat/``.
# They name the device operations of each phase in the compiled program's
# ``op_name`` metadata, and so in a profile; they add no work.  Where phases
# nest, the outermost names the operation.
SCOPE_SEND = "graphmat/send"
SCOPE_GATHER = "graphmat/spmv/gather"
SCOPE_KERNEL = "graphmat/spmv/kernel"
SCOPE_UNPERMUTE = "graphmat/spmv/unpermute"
SCOPE_SPLIT = "graphmat/spmv/split"
SCOPE_SCATTER = "graphmat/spmv/scatter"
SCOPE_MODE = "graphmat/spmv/mode"
SCOPE_APPLY = "graphmat/apply"
SCOPE_INSTALL = "graphmat/install"
SCOPE_EXTRACT = "graphmat/extract"

_SCATTER_FAST = {"add", "min", "max", "any", "all"}
_AXIS_RED = {"add": jnp.sum, "min": jnp.min, "max": jnp.max,
             "any": jnp.any, "all": jnp.all}


def _tree_gather(tree: PyTree, idx: Array) -> PyTree:
  """Gather rows ``tree[idx]`` per leaf (idx may be multi-dimensional)."""
  return jax.tree_util.tree_map(lambda x: x[idx], tree)


def _bcast_mask(mask: Array, leaf: Array) -> Array:
  return mask.reshape(mask.shape + (1,) * (leaf.ndim - mask.ndim))


def _tree_where(mask: Array, a: PyTree, b: PyTree) -> PyTree:
  return jax.tree_util.tree_map(
      lambda x, y: jnp.where(_bcast_mask(mask, x), x, y), a, b)


def mask_inert(msg: PyTree, active: Array, program: GraphProgram) -> PyTree:
  """Replace inactive lanes of ``msg`` with the program's inert message.

  ``active`` may be ``bool[n]`` (whole-vertex frontier) or ``bool[n, Q]``
  (per-query lanes, the batched engine's frontier-in-the-payload encoding).
  Requires ``program.inert_message``.
  """
  if program.inert_message is None:
    raise ValueError(
        f"program {program.name!r} has no inert_message; batched execution "
        "requires one (see GraphProgram.inert_message)")
  return jax.tree_util.tree_map(
      lambda m, i: jnp.where(_bcast_mask(active, m),
                             m, jnp.asarray(i, m.dtype)),
      msg, program.inert_message)


def _vmap_process(program: GraphProgram, batch_dims: int):
  f = program.process_message
  for _ in range(batch_dims):
    f = jax.vmap(f)
  return f


def _axis_tree_reduce(tree: PyTree, red, ident: PyTree, axis: int) -> PyTree:
  """Reduce ``axis`` with a tree-level binary monoid (halving, log₂ steps).

  ``ident`` is a same-structure pytree of identity-filled arrays used to pad
  the axis to a power of two.
  """
  def dim(t):
    return jax.tree_util.tree_leaves(t)[0].shape[axis]

  size = dim(tree)
  pow2 = 1
  while pow2 < size:
    pow2 *= 2
  if pow2 != size:
    pad = pow2 - size
    tree = jax.tree_util.tree_map(
        lambda x, i: jnp.concatenate(
            [x, jax.lax.slice_in_dim(i, 0, pad, axis=axis)], axis=axis),
        tree, ident)
    size = pow2

  def take(t, lo, hi):
    return jax.tree_util.tree_map(
        lambda x: jax.lax.slice_in_dim(x, lo, hi, axis=axis), t)

  while size > 1:
    half = size // 2
    tree = red(take(tree, 0, half), take(tree, half, size))
    size = half
  return jax.tree_util.tree_map(lambda x: jnp.squeeze(x, axis=axis), tree)


# ---------------------------------------------------------------------------
# Dense oracle
# ---------------------------------------------------------------------------


def spmv_dense(adj_vals: Array, adj_struct: Array, msg: PyTree, active: Array,
               dst_prop: PyTree, program: GraphProgram
               ) -> Tuple[PyTree, Array]:
  """O(n²) reference: ``adj_struct[v, u]`` marks edge u→v with value
  ``adj_vals[v, u]``."""
  n = adj_struct.shape[0]
  msg_b = jax.tree_util.tree_map(
      lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), msg)
  prop_b = jax.tree_util.tree_map(
      lambda x: jnp.broadcast_to(x[:, None], (x.shape[0], n) + x.shape[1:]),
      dst_prop)
  r = _vmap_process(program, 2)(msg_b, adj_vals, prop_b)
  valid = adj_struct & active[None, :]
  ident = program.identity_like(r)
  r = _tree_where(valid, r, ident)
  if program.reduce_kind in _SCATTER_FAST:
    axis_red = _AXIS_RED[program.reduce_kind]
    y = jax.tree_util.tree_map(lambda x: axis_red(x, axis=1), r)
  else:
    y = _axis_tree_reduce(r, program.reduce_fn(), ident, axis=1)
  recv = jnp.any(valid, axis=1)
  return y, recv


# ---------------------------------------------------------------------------
# COO: gather + segmented reduce
# ---------------------------------------------------------------------------


def _segment_reduce_fast(r: PyTree, dst: Array, n: int, kind: str,
                         ident: PyTree) -> PyTree:
  """Scatter-based segment reduce for monoids with an ``.at[]`` fast path."""
  # Identity leaves are full arrays shaped like r; take their scalar fill.
  # ``dst`` is non-decreasing (the CooGraph invariant); saying so lets XLA
  # compile a large scatter in seconds instead of tens of seconds.
  def scatter(leaf, ident_leaf):
    fill = ident_leaf.reshape(-1)[0]
    out = jnp.full((n,) + leaf.shape[1:], fill, leaf.dtype)
    upd = out.at[dst]
    kw = dict(mode="drop", indices_are_sorted=True)
    if kind == "add":
      return upd.add(leaf, **kw)
    if kind in ("min", "all"):
      return upd.min(leaf, **kw)
    if kind in ("max", "any"):
      return upd.max(leaf, **kw)
    raise ValueError(kind)
  return jax.tree_util.tree_map(scatter, r, ident)


def _segment_reduce_scan(r: PyTree, dst: Array, n: int, red,
                         ident: PyTree) -> PyTree:
  """Segmented associative scan for generic monoids.

  Requires ``dst`` non-decreasing (graph builders guarantee it).  The scanned
  value at the last edge of each segment is the segment total; it is scattered
  into ``y[dst]`` (one writer per segment, mode="drop" for padded rows).
  """
  e = dst.shape[0]
  starts = jnp.concatenate([jnp.ones((1,), bool), dst[1:] != dst[:-1]])

  def comb(a, b):
    fa, va = a
    fb, vb = b
    v = _tree_where(fb, vb, red(va, vb))
    return (jnp.logical_or(fa, fb), v)

  # associative_scan over pytrees: flatten value tree into the tuple.
  flags_scanned, v_scanned = jax.lax.associative_scan(comb, (starts, r))
  del flags_scanned
  is_last = jnp.concatenate([dst[:-1] != dst[1:], jnp.ones((1,), bool)])
  tgt = jnp.where(is_last, dst, n)  # out-of-bounds for non-last -> dropped

  def scatter(leaf, ident_leaf):
    fill = ident_leaf.reshape(-1)[0]
    out = jnp.full((n,) + leaf.shape[1:], fill, leaf.dtype)
    return out.at[tgt].set(leaf, mode="drop")

  return jax.tree_util.tree_map(scatter, v_scanned, ident)


def segment_mode(lab: Array, dst: Array, valid: Array, n: int
                 ) -> Tuple[Array, Array]:
  """Per destination, the most frequent label of its valid arcs, ties to the
  smallest, and whether it has a valid arc at all.

  Sorts the (dst, label) pairs, so that equal labels of one destination form
  runs; invalid arcs get the sentinel destination ``n``, sort last and are
  dropped.  A label's count is read at the last arc of its run; the winner
  is the smallest label whose run is as long as the destination's longest.
  A destination without a valid arc gets the label dtype's largest value.
  """
  e = dst.shape[0]
  d, lab = jax.lax.sort((jnp.where(valid, dst, n), lab), num_keys=2,
                        is_stable=False)
  pos = jnp.arange(e, dtype=jnp.int32)
  first = jnp.concatenate([jnp.ones((1,), bool),
                           (d[1:] != d[:-1]) | (lab[1:] != lab[:-1])])
  count = pos - jax.lax.cummax(jnp.where(first, pos, 0)) + 1  # so far in run
  kw = dict(mode="drop", indices_are_sorted=True)
  best = jnp.zeros((n,), jnp.int32).at[d].max(count, **kw)
  # A sentinel arc reads a clamped ``best``; its scatter below is dropped.
  top = count == best[d]
  none = jnp.iinfo(lab.dtype).max
  y = jnp.full((n,), none, lab.dtype).at[d].min(jnp.where(top, lab, none),
                                                **kw)
  return y, best > 0


def spmv_coo(g: graphlib.CooGraph, msg: PyTree, active: Array,
             dst_prop: PyTree, program: GraphProgram,
             with_recv: bool = True) -> Tuple[PyTree, Optional[Array]]:
  mode = program.reduce_kind == "mode"
  with jax.named_scope(SCOPE_GATHER):
    m = _tree_gather(msg, g.src)                     # [E, ...]
    if program.process_reads_dst:
      dp = _tree_gather(dst_prop, g.dst)             # [E, ...]
    else:
      dp = _tree_gather(dst_prop, jnp.zeros_like(g.dst))
    r = _vmap_process(program, 1)(m, g.w, dp)        # [E, ...]
    valid = g.emask & active[g.src]
    if not mode:
      ident = program.identity_like(r)
      r = _tree_where(valid, r, ident)
  if mode:
    leaves, treedef = jax.tree_util.tree_flatten(r)
    if (len(leaves) != 1 or leaves[0].ndim != 1
        or not jnp.issubdtype(leaves[0].dtype, jnp.integer)):
      raise TypeError(f"program {program.name!r}: the mode reduce takes one "
                      "integer scalar message per arc")
    with jax.named_scope(SCOPE_MODE):
      y, recv = segment_mode(leaves[0], g.dst, valid, g.n)
    return (jax.tree_util.tree_unflatten(treedef, [y]),
            recv if with_recv else None)
  with jax.named_scope(SCOPE_SCATTER):
    if program.reduce_kind in _SCATTER_FAST:
      y = _segment_reduce_fast(r, g.dst, g.n, program.reduce_kind, ident)
    else:
      y = _segment_reduce_scan(r, g.dst, g.n, program.reduce_fn(), ident)
    if not with_recv:
      return y, None
    recv = jnp.zeros((g.n,), jnp.bool_).at[g.dst].max(
        valid, mode="drop", indices_are_sorted=True)
  return y, recv


# ---------------------------------------------------------------------------
# ELL: gather + reduce over slots (+ the fold of split rows)
# ---------------------------------------------------------------------------


def _ell_packed_compute(g: graphlib.EllGraph, msg: PyTree, active: Array,
                        dst_prop: PyTree, program: GraphProgram):
  """Per-packed-row (y_packed, recv_packed) on the slot-major ELL block: the
  gather, then the reduce over slots that the Pallas kernel does on its
  path (named as the kernel's phase)."""
  with jax.named_scope(SCOPE_GATHER):
    m = _tree_gather(msg, g.cols)                    # [W, n_pad, ...]
    valid = g.mask & active[g.cols]
    if program.process_reads_dst:
      safe_rows = jnp.minimum(g.row_of, g.n - 1)
      dp = _tree_gather(dst_prop, safe_rows)         # [n_pad, ...]
      dp = jax.tree_util.tree_map(
          lambda x: jnp.broadcast_to(x[None], (g.width,) + x.shape), dp)
    else:
      # process_message ignores dst_prop — feed a broadcast dummy row.
      dp = jax.tree_util.tree_map(
          lambda x: jnp.broadcast_to(
              x[:1], (g.width, g.n_pad) + x.shape[1:]), dst_prop)
    r = _vmap_process(program, 2)(m, g.vals, dp)     # [W, n_pad, ...]
    ident = program.identity_like(r)
    r = _tree_where(valid, r, ident)
  with jax.named_scope(SCOPE_KERNEL):
    if program.reduce_kind in _SCATTER_FAST:
      axis_red = _AXIS_RED[program.reduce_kind]
      y_packed = jax.tree_util.tree_map(lambda x: axis_red(x, axis=0), r)
    else:
      y_packed = _axis_tree_reduce(r, program.reduce_fn(), ident, axis=0)
    recv_packed = jnp.any(valid, axis=0)
  return y_packed, recv_packed


def _unpermute(g: graphlib.EllGraph, y_packed: PyTree, recv_packed: Array
               ) -> Tuple[PyTree, Array]:
  """Packed rows back to vertex order: a gather through ``packed_of`` (every
  vertex owns a packed row)."""
  with jax.named_scope(SCOPE_UNPERMUTE):
    return _tree_gather(y_packed, g.packed_of), recv_packed[g.packed_of]


def fold_split(g: graphlib.EllGraph, y: PyTree, recv: Array,
               y_packed: PyTree, recv_packed: Array, program: GraphProgram
               ) -> Tuple[PyTree, Array]:
  """Fold the packed results of the ELL's split rows into their owners'
  ``(y, recv)``: a segment reduce of one entry per split row."""
  if not g.split_rows.shape[0]:
    return y, recv
  with jax.named_scope(SCOPE_SPLIT):
    y_r = _tree_gather(y_packed, g.split_rows)
    ident = program.identity_like(y_r)
    if program.reduce_kind in _SCATTER_FAST:
      y_s = _segment_reduce_fast(y_r, g.split_owner, g.n,
                                 program.reduce_kind, ident)
    else:
      y_s = _segment_reduce_scan(y_r, g.split_owner, g.n,
                                 program.reduce_fn(), ident)
    recv_s = jnp.zeros((g.n,), jnp.bool_).at[g.split_owner].max(
        recv_packed[g.split_rows], mode="drop", indices_are_sorted=True)
    red = program.reduce_fn()
    y = _tree_where(recv_s, _tree_where(recv, red(y, y_s), y_s), y)
    return y, recv | recv_s


def spmv_ell(g: graphlib.EllGraph, msg: PyTree, active: Array,
             dst_prop: PyTree, program: GraphProgram,
             with_recv: bool = True) -> Tuple[PyTree, Optional[Array]]:
  y_packed, recv_packed = _ell_packed_compute(
      g, msg, active, dst_prop, program)
  y, recv = _unpermute(g, y_packed, recv_packed)
  y, recv = fold_split(g, y, recv, y_packed, recv_packed, program)
  return y, (recv if with_recv else None)


# ---------------------------------------------------------------------------
# Partitioned COO: equal-size edge tiles, cache-blocked accumulation
# ---------------------------------------------------------------------------


# Default edge-tile sizing: aim for ~4K-edge tiles (VMEM/cache-blocked
# gathers and scatters), capped so tiny graphs don't over-fragment.
TILE_EDGES = 4096
MAX_TILES = 64


def default_num_tiles(capacity: int) -> int:
  """The paper's "many more partitions than threads" sizing for edge tiles."""
  return max(1, min(MAX_TILES, -(-capacity // TILE_EDGES)))


def spmv_coo_tiled(g: graphlib.CooGraph, msg: PyTree, active: Array,
                   dst_prop: PyTree, program: GraphProgram, *,
                   num_tiles: Optional[int] = None,
                   with_recv: bool = True) -> Tuple[PyTree, Optional[Array]]:
  """Row-partitioned / cache-blocked COO (the paper's load-balancing trick).

  The dst-sorted edge array is cut into ``num_tiles`` *equal-size* contiguous
  tiles — perfectly balanced by construction, the static-shape analogue of
  GraphMat's "many more partitions than threads" — and a ``fori_loop``
  accumulates each tile into the output with the monoid's scatter fast path.
  Because edges are dst-sorted, each tile touches a contiguous destination
  range: the gather of ``dst_prop`` and the scatter into ``y`` are
  cache/VMEM-blocked instead of striding the whole vertex array.

  Per-destination accumulation order is identical to :func:`spmv_coo`'s
  single scatter (ascending edge order from the identity), so results are
  bitwise-equal to the untiled COO backend.

  Requires a scatter-fast monoid (add/min/max/any/all); generic monoids fall
  back to :func:`spmv_coo` at dispatch (see the registry's ``supports``).
  """
  if program.reduce_kind not in _SCATTER_FAST:
    raise ValueError(
        f"spmv_coo_tiled requires a scatter-fast reduce, got "
        f"{program.reduce_kind!r}")
  cap = g.capacity
  t = int(num_tiles) if num_tiles else default_num_tiles(cap)
  t = max(1, min(t, cap))
  ts = -(-cap // t)
  pad = t * ts - cap

  def padded(x, fill):
    if not pad:
      return x.reshape((t, ts) + x.shape[1:])
    tail = jnp.full((pad,) + x.shape[1:], fill, x.dtype)
    return jnp.concatenate([x, tail]).reshape((t, ts) + x.shape[1:])

  # Padded entries: emask=False annihilates them (their processed value is
  # the reduce identity, a no-op under scatter-combine); src/dst stay
  # in-bounds so gathers/scatters never go OOB.
  src = padded(g.src, graphlib.PAD)
  dst = padded(g.dst, max(g.n - 1, 0))
  w = padded(g.w, 0)
  emask = padded(g.emask, False)

  # Output structure from an abstract eval of PROCESS on one edge.
  m_el = jax.tree_util.tree_map(
      lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), msg)
  e_el = jax.ShapeDtypeStruct(g.w.shape[1:], g.w.dtype)
  d_el = jax.tree_util.tree_map(
      lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), dst_prop)
  r_struct = jax.eval_shape(program.process_message, m_el, e_el, d_el)
  proto = jax.tree_util.tree_map(
      lambda s: jnp.zeros((g.n,) + s.shape, s.dtype), r_struct)
  y0 = program.identity_like(proto)
  recv0 = jnp.zeros((g.n,), jnp.bool_) if with_recv else None

  kind = program.reduce_kind

  def scatter(acc, idx, leaf):  # a tile of dst-sorted edges
    upd = acc.at[idx]
    kw = dict(mode="drop", indices_are_sorted=True)
    if kind == "add":
      return upd.add(leaf, **kw)
    if kind in ("min", "all"):
      return upd.min(leaf, **kw)
    return upd.max(leaf, **kw)  # max / any

  def body(i, carry):
    y, recv = carry
    s_t, d_t, w_t, m_t = src[i], dst[i], w[i], emask[i]
    m = _tree_gather(msg, s_t)
    if program.process_reads_dst:
      dp = _tree_gather(dst_prop, d_t)
    else:
      dp = _tree_gather(dst_prop, jnp.zeros_like(d_t))
    r = _vmap_process(program, 1)(m, w_t, dp)
    valid = m_t & active[s_t]
    r = _tree_where(valid, r, program.identity_like(r))
    y = jax.tree_util.tree_map(
        lambda acc, leaf: scatter(acc, d_t, leaf), y, r)
    if recv is not None:
      recv = recv.at[d_t].max(valid, mode="drop", indices_are_sorted=True)
    return y, recv

  y, recv = jax.lax.fori_loop(0, t, body, (y0, recv0))
  return y, recv


# ---------------------------------------------------------------------------
# Dispatch (plan-based: repro.core.backends owns the registry)
# ---------------------------------------------------------------------------


def spmv(graph, msg: PyTree, active: Array, dst_prop: PyTree,
         program: GraphProgram, *, backend=None,
         with_recv: bool = True) -> Tuple[PyTree, Optional[Array]]:
  """Generalized SpMV dispatcher.

  ``backend`` is a :class:`repro.core.backends.Plan`, a registered backend
  name (legacy string shim), or None/"auto" for structural selection.  The
  registry (:mod:`repro.core.backends`) resolves the executing backend; the
  old if/elif chain lives on as the built-ins' ``supports``/``eligible``
  predicates.
  """
  from repro.core import backends as backends_lib  # lazy: avoid import cycle
  plan = backends_lib.as_plan(backend)
  impl = backends_lib.resolve(plan, graph, msg, dst_prop, program)
  return impl.execute(graph, msg, active, dst_prop, program, plan, with_recv)


def _pallas_eligible(g: graphlib.EllGraph, msg: PyTree, dst_prop: PyTree,
                     program: GraphProgram) -> bool:
  # The Pallas kernel handles single-leaf scalar messages, or [n, Q] lanes of
  # a lanewise program, with add/min/max reductions; everything else uses
  # the jnp ELL backend.
  leaves = jax.tree_util.tree_leaves(msg)
  if len(leaves) != 1 or program.reduce_kind not in ("add", "min", "max"):
    return False
  ndim = leaves[0].ndim
  if ndim > 2 or (ndim == 2 and not program.lanewise):
    return False
  dp_leaves = jax.tree_util.tree_leaves(dst_prop)
  return (not program.process_reads_dst) or (
      len(dp_leaves) == 1 and dp_leaves[0].ndim <= ndim)
