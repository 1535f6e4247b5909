"""ELL rows of hubs split across packed rows of the same block.

``build_ell`` gives a vertex of in-degree d ``ceil(d / W)`` packed rows: its
home row and split rows of up to W edges each, all sorted by length.  The
layout tests hold the container to that; the agreement tests hold one
superstep of the jnp ELL path and of the Pallas path (interpreted here),
split rows folded in, to the COO and dense backends.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.algos.multi import multi_sssp_program
from repro.core import graph as G
from repro.core import spmv as S
from repro.core.vertex_program import GraphProgram
from repro.graphs import dedupe_edges
from repro.kernels.ops import spmv_ell_pallas

W = 8


def star(n=160, hub=5, seed=0):
  """Every other vertex points at ``hub`` (in-degree ≥ 159 ≥ 10 W), plus a
  sparse random graph around it; no edge twice."""
  rng = np.random.default_rng(seed)
  others = np.delete(np.arange(n), hub)
  src = np.concatenate([others, rng.integers(0, n, 3 * n)])
  dst = np.concatenate([np.full(n - 1, hub), rng.integers(0, n, 3 * n)])
  keep = src != dst
  src, dst = dedupe_edges(src[keep], dst[keep])
  w = rng.uniform(0.1, 2.0, src.size).astype(np.float32)
  return n, src, dst, w


@pytest.fixture(scope="module", params=["rmat", "star"])
def hub_graph(request, rmat_small):
  """(n, src, dst, w, W): edges whose hub rows exceed the slot width."""
  if request.param == "rmat":
    return rmat_small + (W,)
  n, src, dst, w = star()
  assert np.bincount(dst, minlength=n).max() >= 10 * W
  return n, src, dst, w, W


def old_layout(src, dst, w, n, width, row_block=128):
  """The degree-sorted layout of a graph with no row above ``width``: rows
  in stable descending in-degree order, edges in destination order."""
  in_deg = np.bincount(dst, minlength=n)
  perm = np.argsort(-in_deg, kind="stable")
  inv = np.empty(n, np.int64)
  inv[perm] = np.arange(n)
  n_pad = -(-n // row_block) * row_block
  cols = np.zeros((width, n_pad), np.int32)
  mask = np.zeros((width, n_pad), bool)
  order = np.argsort(dst, kind="stable")
  s_src, s_dst = src[order], dst[order]
  slot = np.arange(s_dst.size) - np.searchsorted(s_dst, s_dst)
  cols[slot, inv[s_dst]] = s_src
  mask[slot, inv[s_dst]] = True
  return perm, inv, cols, mask


# ---------------------------------------------------------------------------
# Layout
# ---------------------------------------------------------------------------


# rmat at W: tests/test_system.py::test_ell_roundtrip.
@pytest.mark.parametrize("kind,width", [("rmat", None), ("star", W),
                                        ("star", None)])
def test_every_arc_placed_once(rmat_small, kind, width):
  n, src, dst, w = rmat_small if kind == "rmat" else star()
  g = G.build_ell(src, dst, w, n=n, width=width)
  s2, d2, w2 = G.coo_from_ell(g)
  assert (sorted(zip(src.tolist(), dst.tolist(), w.tolist()))
          == sorted(zip(s2.tolist(), d2.tolist(), w2.tolist())))


def test_rows_of_each_vertex(hub_graph):
  """Each vertex owns ceil(d / W) rows (at least one), every one of them
  full but its last; no row holds more than W edges."""
  n, src, dst, w, width = hub_graph
  g = G.build_ell(src, dst, w, n=n, width=width)
  in_deg = np.bincount(dst, minlength=n)
  assert G.ell_split(in_deg, width)[0] == width
  rows = G.ell_split(in_deg, width)[1]
  mask, row_of = np.asarray(g.mask), np.asarray(g.row_of)
  length = mask.sum(axis=0)
  real = row_of < n
  assert (length <= width).all() and not length[~real].any()
  np.testing.assert_array_equal(np.bincount(row_of[real], minlength=n), rows)
  np.testing.assert_array_equal(
      np.bincount(row_of[real], weights=length[real], minlength=n), in_deg)
  full = np.bincount(row_of[real], weights=length[real] == width,
                     minlength=n)
  np.testing.assert_array_equal(full, in_deg // width)
  # A row's edges fill its first slots.
  assert (mask == (np.arange(width)[:, None] < length[None])).all()
  assert rows.max() > 1


def test_split_rows_and_owners(hub_graph):
  n, src, dst, w, width = hub_graph
  g = G.build_ell(src, dst, w, n=n, width=width)
  split, owner = np.asarray(g.split_rows), np.asarray(g.split_owner)
  packed_of, row_of = np.asarray(g.packed_of), np.asarray(g.row_of)
  rows = G.ell_split(np.bincount(dst, minlength=n), width)[1]
  assert split.size == int((rows - 1).sum()) > 0
  assert (np.diff(owner) >= 0).all()
  np.testing.assert_array_equal(row_of[split], owner)
  np.testing.assert_array_equal(row_of[packed_of], np.arange(n))
  # Home and split rows are distinct packed rows, and cover every owned row.
  both = np.concatenate([packed_of, split])
  assert np.unique(both).size == both.size == int((row_of < n).sum())
  assert g.n_pad == -(-(n + split.size) // 128) * 128


def test_slot_rows_bound_every_slot(hub_graph):
  """Rows are sorted by length, so slot s is filled in a prefix of the
  packed rows; ``slot_rows`` is that prefix rounded up to whole row blocks,
  non-increasing, and together the extents count every arc."""
  n, src, dst, w, width = hub_graph
  g = G.build_ell(src, dst, w, n=n, width=width)
  mask = np.asarray(g.mask)
  exact = mask.sum(axis=1)
  assert exact.sum() == src.size
  assert list(g.slot_rows) == sorted(g.slot_rows, reverse=True)
  for s, r in enumerate(g.slot_rows):
    assert mask[s, :exact[s]].all() and not mask[s, exact[s]:].any()
    assert r == min(g.n_pad, -(-int(exact[s]) // 128) * 128)


def test_no_row_above_width_keeps_the_layout(rmat_small):
  """A graph whose rows all fit gets no split rows and the degree-sorted
  layout of one row per vertex."""
  n, src, dst, w = rmat_small
  width = -(-int(np.bincount(dst, minlength=n).max()) // 8) * 8
  g = G.build_ell(src, dst, w, n=n, width=width)
  perm, inv, cols, mask = old_layout(src, dst, w, n, width)
  assert g.split_rows.shape == g.split_owner.shape == (0,)
  assert g.n_pad == 256
  np.testing.assert_array_equal(np.asarray(g.row_of)[:n], perm)
  np.testing.assert_array_equal(np.asarray(g.packed_of), inv)
  np.testing.assert_array_equal(np.asarray(g.cols), cols)
  np.testing.assert_array_equal(np.asarray(g.mask), mask)


# ---------------------------------------------------------------------------
# Agreement, one superstep
# ---------------------------------------------------------------------------


PATHS = {"ell": S.spmv_ell, "pallas": spmv_ell_pallas}


def inputs(n, seed, lanes=None):
  rng = np.random.default_rng(seed)
  shape = (n,) if lanes is None else (n, lanes)
  msg = jnp.asarray(rng.uniform(0, 5, shape).astype(np.float32))
  act = jnp.asarray(rng.uniform(size=n) > 0.4)
  return msg, act


def superstep(hub_graph, program, msg, act, prop, path):
  n, src, dst, w, width = hub_graph
  ell = G.build_ell(src, dst, w, n=n, width=width)
  coo = G.build_coo(src, dst, w, n=n)
  got = PATHS[path](ell, msg, act, prop, program)
  want = S.spmv_coo(coo, msg, act, prop, program)
  return got, want


def dense(hub_graph, program, msg, act, prop):
  n, src, dst, w, _ = hub_graph
  adj_v, adj_s = G.dense_adjacency(src, dst, w, n=n)
  return S.spmv_dense(adj_v, adj_s, msg, act, prop, program)


MIN_PLUS = GraphProgram(process_message=lambda m, e, d: m + e,
                        reduce_kind="min", process_reads_dst=False)
PLUS_TIMES = GraphProgram(process_message=lambda m, e, d: m * e,
                          reduce_kind="add", process_reads_dst=False)
# Reads the destination's property: min over in-edges of max(m, d) + e.
MIN_DST = GraphProgram(process_message=lambda m, e, d: jnp.maximum(m, d) + e,
                       reduce_kind="min", process_reads_dst=True)


@pytest.mark.parametrize("path", sorted(PATHS))
def test_min_is_bitwise(hub_graph, path):
  msg, act = inputs(hub_graph[0], 1)
  (y, r), (y_c, r_c) = superstep(hub_graph, MIN_PLUS, msg, act, msg, path)
  y_d, r_d = dense(hub_graph, MIN_PLUS, msg, act, msg)
  for yy, rr in ((y_c, r_c), (y_d, r_d)):
    np.testing.assert_array_equal(np.asarray(r), np.asarray(rr))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yy))


@pytest.mark.parametrize("path", sorted(PATHS))
def test_add_within_float_rounding(hub_graph, path):
  msg, act = inputs(hub_graph[0], 2)
  (y, r), (y_c, r_c) = superstep(hub_graph, PLUS_TIMES, msg, act, msg, path)
  y_d, r_d = dense(hub_graph, PLUS_TIMES, msg, act, msg)
  for yy, rr in ((y_c, r_c), (y_d, r_d)):
    np.testing.assert_array_equal(np.asarray(r), np.asarray(rr))
    np.testing.assert_allclose(np.asarray(y), np.asarray(yy), rtol=1e-6)


def test_generic_monoid_through_the_scan(hub_graph):
  """A generic monoid (a pair: max value, and the count of messages) folds
  its split rows through the segmented scan."""
  prog = GraphProgram(
      process_message=lambda m, e, d: (m * e, jnp.ones_like(m)),
      reduce_kind="generic",
      reduce=lambda a, b: (jnp.maximum(a[0], b[0]), a[1] + b[1]),
      reduce_identity=(-jnp.inf, 0.0), process_reads_dst=False)
  msg, act = inputs(hub_graph[0], 3)
  (y, r), (y_c, r_c) = superstep(hub_graph, prog, msg, act, msg, "ell")
  np.testing.assert_array_equal(np.asarray(r), np.asarray(r_c))
  for a, b in zip(y, y_c):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
  n, src, dst, _, _ = hub_graph
  counts = np.bincount(dst, weights=np.asarray(act)[src], minlength=n)
  np.testing.assert_array_equal(np.asarray(y[1]), counts)


@pytest.mark.parametrize("path", sorted(PATHS))
def test_program_that_reads_dst(hub_graph, path):
  """Split rows read their owner's property through ``row_of``."""
  msg, act = inputs(hub_graph[0], 4)
  prop = jnp.asarray(np.random.default_rng(5).uniform(
      0, 3, hub_graph[0]).astype(np.float32))
  (y, r), (y_c, r_c) = superstep(hub_graph, MIN_DST, msg, act, prop, path)
  y_d, r_d = dense(hub_graph, MIN_DST, msg, act, prop)
  for yy, rr in ((y_c, r_c), (y_d, r_d)):
    np.testing.assert_array_equal(np.asarray(r), np.asarray(rr))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yy))


@pytest.mark.parametrize("path", sorted(PATHS))
def test_batched_sssp_lanes(hub_graph, path):
  """The ``[n, Q]`` lanes of ``multi_sssp_program``, inactive lanes inert."""
  n = hub_graph[0]
  prog = multi_sssp_program()
  msg, act = inputs(n, 6, lanes=4)
  lane_on = np.random.default_rng(7).uniform(size=(n, 4)) > 0.3
  msg = jnp.where(jnp.asarray(lane_on), msg, jnp.inf)
  (y, r), (y_c, r_c) = superstep(hub_graph, prog, msg, act, msg, path)
  y_d, r_d = dense(hub_graph, prog, msg, act, msg)
  assert y.shape == (n, 4)
  for yy, rr in ((y_c, r_c), (y_d, r_d)):
    np.testing.assert_array_equal(np.asarray(r), np.asarray(rr))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yy))
