"""Graph containers — the TPU-native answer to the paper's DCSC partitions.

The paper stores the transposed adjacency matrix as 1-D row-partitioned DCSC
(hypersparse CSC) and walks columns with pointer arithmetic.  That layout is
built for cache hierarchies and scalar/AVX cores; a systolic/vector machine
wants *static shapes and unit-stride loads*.  We therefore provide:

* :class:`CooGraph` — edge list sorted by destination, padded to capacity.
  The "many more partitions than threads" load-balancing trick of the paper
  becomes tiling the edge array into equal-size tiles: perfectly balanced by
  construction.  Backend: gather + segmented reduce.
* :class:`EllGraph` — degree-sorted ELLPACK rows (SELL-σ-style permutation)
  with a fixed slot width, hub rows split into further rows of the same
  block, stored slot-major (``[width, n_pad]``) so packed rows lie along the
  TPU's lane axis.  This is the VMEM-tileable format the Pallas kernel
  consumes.
* ``dense_adjacency`` — small-graph oracle.

All containers are registered pytrees of ``jax.Array``s with static metadata,
so they can cross ``jit``/``shard_map``/``while_loop`` boundaries.

Orientation convention: we store edges (src → dst) and compute *pull-mode*
SpMV ``y = A^T ⊗ x`` exactly as the paper does (messages flow along edges into
their destination), i.e. for every edge ``(u, v)``: ``y[v] ⊕= process(x[u],
w_uv, prop[v])``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

# Sentinel column index for padded ELL slots / padded COO entries.  Points at
# vertex 0 so gathers stay in-bounds; a mask kills the contribution.
PAD = 0


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class CooGraph:
  """Destination-sorted COO with static capacity.

  Arrays are padded to ``capacity`` edges; ``emask`` marks real edges.
  ``src``/``dst`` of padded entries point at vertex 0 (in-bounds).
  """

  n: int                 # static: number of vertices
  src: Array             # int32[capacity]
  dst: Array             # int32[capacity], non-decreasing (padding: n-1)
  w: Array               # edge values [capacity] (ones if unweighted)
  emask: Array           # bool[capacity]
  out_deg: Array         # int32[n]
  in_deg: Array          # int32[n]

  # -- pytree protocol --
  def tree_flatten(self):
    return ((self.src, self.dst, self.w, self.emask, self.out_deg,
             self.in_deg), (self.n,))

  @classmethod
  def tree_unflatten(cls, aux, children):
    return cls(aux[0], *children)

  @property
  def capacity(self) -> int:
    return int(self.src.shape[0])

  @property
  def num_edges(self) -> Array:
    return jnp.sum(self.emask.astype(jnp.int32))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class EllGraph:
  """Degree-sorted blocked-ELL with hub rows split across packed rows.

  A vertex of in-degree d owns ``ceil(d / width)`` packed rows (at least
  one): its home row, ``packed_of[v]``, and one *split row* for each further
  ``width`` of its incoming edges.  All rows are sorted by their own length,
  so padding waste within a slot block is bounded; the split rows' results
  are folded into their owners after the reduce over slots.

  ``cols[s, r]`` is the *source* vertex of the s-th incoming edge of packed
  row r (slot-major: rows are the minor, lane-dense axis); ``row_of[r]``
  maps packed row -> owner vertex id; ``packed_of[v]`` is the home row of
  vertex v.  ``split_rows`` lists the packed positions of the split rows,
  ``split_owner`` their owners, non-decreasing.  Rows are sorted by length,
  so slot s holds edges only in packed rows ``[0, slot_rows[s])``, a
  non-increasing extent the Pallas kernel uses to skip padding (None: every
  row).
  """

  n: int                 # static: number of vertices
  width: int             # static: ELL slot width
  cols: Array            # int32[width, n_pad]  (source vertex ids)
  vals: Array            # [width, n_pad]       (edge values)
  mask: Array            # bool[width, n_pad]
  row_of: Array          # int32[n_pad]  packed row -> owner vertex id
  packed_of: Array       # int32[n]      vertex id -> home packed row
  split_rows: Array      # int32[V]      packed rows beyond the home rows
  split_owner: Array     # int32[V]      their owners, non-decreasing
  slot_rows: Optional[Tuple[int, ...]] = None  # static: row extent per slot

  def tree_flatten(self):
    children = (self.cols, self.vals, self.mask, self.row_of, self.packed_of,
                self.split_rows, self.split_owner)
    return children, (self.n, self.width, self.slot_rows)

  @classmethod
  def tree_unflatten(cls, aux, children):
    n, width, slot_rows = aux
    return cls(n, width, *children, slot_rows=slot_rows)

  @property
  def n_pad(self) -> int:
    return int(self.cols.shape[1])


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class DenseGraph:
  """O(n²) dense-adjacency container — the oracle, runnable end-to-end.

  ``struct[v, u]`` marks edge u→v with value ``vals[v, u]``.  Routes through
  :func:`repro.core.spmv.spmv_dense`; only sensible for small graphs, but it
  exercises the identical engine/program surface as COO/ELL, which makes it
  the reference backend for equivalence tests (including the batched
  multi-query engine).
  """

  n: int                 # static: number of vertices
  vals: Array            # [n, n] edge values
  struct: Array          # bool[n, n] structure mask

  def tree_flatten(self):
    return ((self.vals, self.struct), (self.n,))

  @classmethod
  def tree_unflatten(cls, aux, children):
    return cls(aux[0], *children)


# ---------------------------------------------------------------------------
# Host-side constructors (data-pipeline; numpy, not traced).
# ---------------------------------------------------------------------------


def _as_np_edges(src, dst, w, n, dtype):
  src = np.asarray(src, np.int32)
  dst = np.asarray(dst, np.int32)
  if w is None:
    w = np.ones(src.shape[0], dtype)
  else:
    w = np.asarray(w, dtype)
  assert src.shape == dst.shape == w.shape
  if src.size:
    assert src.max(initial=0) < n and dst.max(initial=0) < n
  return src, dst, w


def build_coo(src, dst, w=None, *, n: int, edge_dtype=jnp.float32,
              capacity: Optional[int] = None, sort: bool = True) -> CooGraph:
  """Build a destination-sorted :class:`CooGraph` from host edge arrays."""
  dt = np.dtype(edge_dtype)
  src, dst, w = _as_np_edges(src, dst, w, n, dt)
  if sort and src.size:
    order = np.argsort(dst, kind="stable")
    src, dst, w = src[order], dst[order], w[order]
  e = src.shape[0]
  cap = capacity or max(e, 1)
  assert cap >= e, f"capacity {cap} < num edges {e}"
  pad = cap - e
  emask = np.concatenate([np.ones(e, bool), np.zeros(pad, bool)])
  src_p = np.concatenate([src, np.full(pad, PAD, np.int32)])
  # Padded dst = n-1 keeps the array destination-sorted (required by the
  # segmented-scan reduce path); emask annihilates the contribution.
  dst_p = np.concatenate([dst, np.full(pad, max(n - 1, 0), np.int32)])
  w_p = np.concatenate([w, np.zeros(pad, dt)])
  out_deg = np.bincount(src, minlength=n).astype(np.int32)
  in_deg = np.bincount(dst, minlength=n).astype(np.int32)
  return CooGraph(
      n=n,
      src=jnp.asarray(src_p),
      dst=jnp.asarray(dst_p),
      w=jnp.asarray(w_p),
      emask=jnp.asarray(emask),
      out_deg=jnp.asarray(out_deg),
      in_deg=jnp.asarray(in_deg),
  )


def ell_split(in_deg, width: Optional[int] = None
              ) -> Tuple[int, np.ndarray]:
  """The ELL's slot width and the number of packed rows of each vertex.

  ``width`` defaults to the 95th-percentile nonzero in-degree rounded up to
  a multiple of 8.  A vertex of in-degree d holds ``max(1, ceil(d / width))``
  rows: its first ``width`` incoming edges in its home row, each further
  ``width`` in a split row.
  """
  in_deg = np.asarray(in_deg, np.int64)
  if width is None:
    nz = in_deg[in_deg > 0]
    q = int(np.percentile(nz, 95)) if nz.size else 1
    width = max(8, int(np.ceil(q / 8)) * 8)
  return int(width), np.maximum(1, -(-in_deg // width))


def build_ell(src, dst, w=None, *, n: int, edge_dtype=jnp.float32,
              width: Optional[int] = None, row_block: int = 128) -> EllGraph:
  """Build a degree-sorted :class:`EllGraph` from host edges.

  Args:
    width: ELL slot width (default: :func:`ell_split`'s).  A row of higher
      in-degree continues in split rows of the same block.
    row_block: pad packed rows to a multiple of this (the Pallas row tile
      is a multiple of the 128-wide lane axis).
  """
  dt = np.dtype(edge_dtype)
  src, dst, w = _as_np_edges(src, dst, w, n, dt)
  in_deg = np.bincount(dst, minlength=n).astype(np.int64)
  width, pieces = ell_split(in_deg, width)

  # Rows 0..n-1 are the vertices' home rows, then the split rows in owner
  # order; each holds up to `width` of its owner's edges.
  extra = pieces - 1
  first = np.cumsum(extra) - extra             # first split row, per vertex
  owner = np.repeat(np.arange(n, dtype=np.int32), extra)
  piece = np.arange(owner.size) - first[owner] + 1
  length = np.concatenate([np.minimum(in_deg, width),
                           np.minimum(width, in_deg[owner] - piece * width)])
  owner_of = np.concatenate([np.arange(n, dtype=np.int32), owner])

  # Length-sorted row permutation (descending) — the SELL-σ idea with σ = all
  # rows: dense rows cluster together, padding waste concentrates in few
  # tiles.  With no split rows this is the in-degree order.
  perm = np.argsort(-length, kind="stable")                   # packed -> row
  pos = np.empty(perm.size, np.int32)
  pos[perm] = np.arange(perm.size, dtype=np.int32)            # row -> packed

  n_pad = int(np.ceil(perm.size / row_block)) * row_block
  cols = np.full((width, n_pad), PAD, np.int32)
  vals = np.zeros((width, n_pad), dt)
  mask = np.zeros((width, n_pad), bool)

  # Position of each edge within its destination's edges: its row and slot.
  order = np.argsort(dst, kind="stable")
  s_src, s_dst, s_w = src[order], dst[order], w[order]
  k = np.arange(s_dst.shape[0]) - np.searchsorted(s_dst, s_dst)
  p = k // width
  row = np.where(p == 0, s_dst, n + first[s_dst] + p - 1)
  slot, r = k % width, pos[row]
  cols[slot, r] = s_src
  vals[slot, r] = s_w
  mask[slot, r] = True

  # Rows longer than s, rounded up to whole row blocks.
  deeper = perm.size - np.cumsum(np.bincount(length,
                                             minlength=width + 1))[:width]
  slot_rows = tuple(min(n_pad, -(-int(c) // row_block) * row_block)
                    for c in deeper)

  # Padded packed rows map to vertex `n` (out of bounds): the un-permute
  # gathers through `packed_of`, which never selects them; gathers through
  # `row_of` clip and are masked.
  row_of = np.concatenate([owner_of[perm],
                           np.full(n_pad - perm.size, n, np.int32)])
  return EllGraph(
      n=n, width=width,
      cols=jnp.asarray(cols), vals=jnp.asarray(vals), mask=jnp.asarray(mask),
      row_of=jnp.asarray(row_of), packed_of=jnp.asarray(pos[:n]),
      split_rows=jnp.asarray(pos[n:]), split_owner=jnp.asarray(owner),
      slot_rows=slot_rows)


def dense_adjacency(src, dst, w=None, *, n: int,
                    edge_dtype=jnp.float32) -> Tuple[Array, Array]:
  """Small-graph oracle: (A[dst, src] value matrix, boolean structure)."""
  dt = np.dtype(edge_dtype)
  src, dst, w = _as_np_edges(src, dst, w, n, dt)
  a = np.zeros((n, n), dt)
  s = np.zeros((n, n), bool)
  a[dst, src] = w
  s[dst, src] = True
  return jnp.asarray(a), jnp.asarray(s)


def build_dense(src, dst, w=None, *, n: int,
                edge_dtype=jnp.float32) -> DenseGraph:
  """Build a :class:`DenseGraph` from host edge arrays."""
  vals, struct = dense_adjacency(src, dst, w, n=n, edge_dtype=edge_dtype)
  return DenseGraph(n=n, vals=vals, struct=struct)


def coo_from_ell(g: EllGraph) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
  """Host-side: recover (src, dst, w) from an EllGraph (tests/round-trips)."""
  cols = np.asarray(g.cols)
  vals = np.asarray(g.vals)
  mask = np.asarray(g.mask)
  row_of = np.asarray(g.row_of)
  ss, rr = np.nonzero(mask)
  src = cols[ss, rr]
  dst = row_of[rr]
  w = vals[ss, rr]
  return src, dst, w
