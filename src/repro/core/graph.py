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
  with a fixed slot width and a COO spill for hub rows, stored slot-major
  (``[width, n_pad]``) so packed rows lie along the TPU's lane axis.  This
  is the VMEM-tileable format the Pallas kernel consumes.
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
  """Degree-sorted blocked-ELL + COO spill.

  Rows (destination vertices) are permuted by in-degree so that padding waste
  within a slot block is bounded; rows with in-degree > ``width`` spill their
  excess edges into a COO tail that is processed by the segment backend.

  ``cols[s, r]`` is the *source* vertex of the s-th incoming edge of packed
  row r (slot-major: rows are the minor, lane-dense axis); ``row_of[r]``
  maps packed row -> vertex id; ``packed_of[v]`` is the inverse
  permutation.  Rows are sorted by in-degree, so slot s holds edges only in
  packed rows ``[0, slot_rows[s])``, a non-increasing extent the Pallas
  kernel uses to skip padding (None: every row).
  """

  n: int                 # static: number of vertices
  width: int             # static: ELL slot width
  cols: Array            # int32[width, n_pad]  (source vertex ids)
  vals: Array            # [width, n_pad]       (edge values)
  mask: Array            # bool[width, n_pad]
  row_of: Array          # int32[n_pad]  packed row -> vertex id
  packed_of: Array       # int32[n]      vertex id -> packed row
  spill: Optional[CooGraph]  # hub-row excess edges (or None)
  slot_rows: Optional[Tuple[int, ...]] = None  # static: row extent per slot

  def tree_flatten(self):
    children = (self.cols, self.vals, self.mask, self.row_of, self.packed_of,
                self.spill)
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


def build_ell(src, dst, w=None, *, n: int, edge_dtype=jnp.float32,
              width: Optional[int] = None, row_block: int = 128,
              spill_frac_cap: float = 1.0) -> EllGraph:
  """Build a degree-sorted :class:`EllGraph` (+ spill) from host edges.

  Args:
    width: ELL slot width.  Default: the 95th-percentile in-degree rounded up
      to a multiple of 8 — hub rows beyond it spill to COO (hybrid format).
    row_block: pad packed rows to a multiple of this (the Pallas row tile
      is a multiple of the 128-wide lane axis).
    spill_frac_cap: sanity cap on the fraction of edges allowed to spill.
  """
  dt = np.dtype(edge_dtype)
  src, dst, w = _as_np_edges(src, dst, w, n, dt)
  in_deg = np.bincount(dst, minlength=n).astype(np.int32)
  if width is None:
    nz = in_deg[in_deg > 0]
    q = int(np.percentile(nz, 95)) if nz.size else 1
    width = max(8, int(np.ceil(q / 8)) * 8)

  # Degree-sorted row permutation (descending) — the SELL-σ idea with σ = n:
  # dense rows cluster together, padding waste concentrates in few tiles.
  perm = np.argsort(-in_deg, kind="stable").astype(np.int32)  # packed -> vid
  inv = np.empty(n, np.int32)
  inv[perm] = np.arange(n, dtype=np.int32)                    # vid -> packed

  n_pad = int(np.ceil(n / row_block)) * row_block
  cols = np.full((width, n_pad), PAD, np.int32)
  vals = np.zeros((width, n_pad), dt)
  mask = np.zeros((width, n_pad), bool)

  # Slot position of each edge within its destination row.
  order = np.argsort(dst, kind="stable")
  s_src, s_dst, s_w = src[order], dst[order], w[order]
  if s_dst.size:
    starts = np.searchsorted(s_dst, s_dst)  # first index of this dst run
    slot = np.arange(s_dst.shape[0]) - starts
  else:
    slot = np.zeros(0, np.int64)
  fits = slot < width
  r = inv[s_dst[fits]]
  cols[slot[fits], r] = s_src[fits]
  vals[slot[fits], r] = s_w[fits]
  mask[slot[fits], r] = True

  spill_src, spill_dst, spill_w = s_src[~fits], s_dst[~fits], s_w[~fits]
  total = max(src.shape[0], 1)
  assert spill_src.shape[0] <= spill_frac_cap * total, (
      f"{spill_src.shape[0]}/{total} edges spill; raise width")
  spill = None
  if spill_src.shape[0]:
    spill = build_coo(spill_src, spill_dst, spill_w, n=n, edge_dtype=dt)
  # Rows with in-degree > s, rounded up to whole row blocks.
  deeper = n - np.cumsum(np.bincount(np.minimum(in_deg, width),
                                     minlength=width + 1))[:width]
  slot_rows = tuple(min(n_pad, -(-int(c) // row_block) * row_block)
                    for c in deeper)

  # Padded packed rows map to vertex `n` (out of bounds): the un-permute
  # gathers through `packed_of`, which never selects them; gathers through
  # `row_of` clip and are masked.
  row_of = np.concatenate(
      [perm, np.full(n_pad - n, n, np.int32)]) if n_pad > n else perm
  return EllGraph(
      n=n, width=int(width),
      cols=jnp.asarray(cols), vals=jnp.asarray(vals), mask=jnp.asarray(mask),
      row_of=jnp.asarray(row_of), packed_of=jnp.asarray(inv), spill=spill,
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
  if g.spill is not None:
    em = np.asarray(g.spill.emask)
    src = np.concatenate([src, np.asarray(g.spill.src)[em]])
    dst = np.concatenate([dst, np.asarray(g.spill.dst)[em]])
    w = np.concatenate([w, np.asarray(g.spill.w)[em]])
  return src, dst, w
