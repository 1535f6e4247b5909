"""CDLP (LDBC Graphalytics label propagation) and the ``mode`` reduce.

``cdlp`` runs through ``run_fixed_iters`` -> ``spmv`` -> the COO backend's
segmented mode reduce; ``native_cdlp`` is the plain numpy reference.  The
mode reduce is no monoid, so every other backend, and the 2-D runners,
refuse it rather than compute something else.
"""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.algos.cdlp import cdlp, cdlp_program
from repro.algos.native import native_cdlp
from repro.algos.pagerank import pagerank_program
from repro.core import backends as B
from repro.core import distributed as D
from repro.core import graph as G
from repro.core.spmv import segment_mode, spmv
from repro.core.vertex_program import GraphProgram
from repro.graphs import dedupe_edges, remove_self_loops, rmat_edges


def _rmat(scale, seed):
  """A symmetric RMAT graph, both arcs of every edge (as Graphalytics)."""
  src, dst = rmat_edges(scale, 8, seed=seed)
  src, dst = dedupe_edges(*remove_self_loops(src, dst))
  return (1 << scale, np.concatenate([src, dst]).astype(np.int32),
          np.concatenate([dst, src]).astype(np.int32))


def _brute_cdlp(src, dst, n, num_iters):
  """Vertex by vertex with a Counter: the definition, word for word."""
  labels = list(range(n))
  ins = collections.defaultdict(list)
  for u, v in zip(src.tolist(), dst.tolist()):
    ins[v].append(u)
  for _ in range(num_iters):
    new = list(labels)
    for v, us in ins.items():
      counts = collections.Counter(labels[u] for u in us)
      top = max(counts.values())
      new[v] = min(lab for lab, c in counts.items() if c == top)
    labels = new
  return np.asarray(labels, np.int32)


@pytest.mark.parametrize("num_iters", [1, 2, 10])
@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("scale", [8, 10])
def test_cdlp_matches_native(scale, seed, num_iters):
  n, src, dst = _rmat(scale, seed)
  g = G.build_coo(src, dst, n=n)
  got = np.asarray(cdlp(g, num_iters, backend=B.Plan("coo")))
  np.testing.assert_array_equal(got, native_cdlp(src, dst, n, num_iters))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_native_cdlp_is_the_definition(seed):
  """On a small multigraph with repeated arcs, so that counts tie often."""
  rng = np.random.default_rng(seed)
  n = 40
  src = rng.integers(0, n, 300).astype(np.int32)
  dst = rng.integers(0, n - 5, 300).astype(np.int32)   # 5 without in-arcs
  for it in (1, 3):
    np.testing.assert_array_equal(native_cdlp(src, dst, n, it),
                                  _brute_cdlp(src, dst, n, it))


@pytest.mark.parametrize("case", ["two_way_tie", "isolated", "padding"])
def test_cdlp_by_hand(case):
  """A two-way tie takes the smaller label; vertices without in-arcs keep
  their labels; capacity padding (src 0, dst n-1) never votes."""
  n = 8
  # Vertex 5 hears 3 twice and 1 twice and 2 once: 1 and 3 tie.
  src = np.array([3, 1, 3, 2, 1, 0, 4], np.int32)
  dst = np.array([5, 5, 5, 5, 5, 1, 0], np.int32)
  cap = src.size + (9 if case == "padding" else 0)
  g = G.build_coo(src, dst, n=n, capacity=cap)
  got = np.asarray(cdlp(g, 1, backend=B.Plan("coo")))
  np.testing.assert_array_equal(got, native_cdlp(src, dst, n, 1))
  if case == "two_way_tie":
    assert got[5] == 1
  elif case == "isolated":
    assert got[0] == 4 and got[1] == 0
    np.testing.assert_array_equal(got[[2, 3, 4, 6, 7]], [2, 3, 4, 6, 7])
  else:
    assert got[n - 1] == n - 1          # padding would have voted label 0


@pytest.mark.parametrize("seed", range(6))
def test_segment_mode_matches_unique(seed):
  rng = np.random.default_rng(seed)
  n, e = 30, int(rng.integers(1, 400))
  dst = np.sort(rng.integers(0, n, e)).astype(np.int32)
  lab = rng.integers(-3, 6 + seed * 50, e).astype(np.int32)
  valid = rng.random(e) < 0.8
  y, recv = jax.jit(segment_mode, static_argnums=3)(
      jnp.asarray(lab), jnp.asarray(dst), jnp.asarray(valid), n)
  y, recv = np.asarray(y), np.asarray(recv)
  for v in range(n):
    mine = lab[(dst == v) & valid]
    assert recv[v] == (mine.size > 0)
    if mine.size:
      values, counts = np.unique(mine, return_counts=True)
      assert y[v] == values[np.argmax(counts)]   # first maximum: smallest


def test_mode_program_is_checked():
  with pytest.raises(ValueError):
    GraphProgram(process_message=lambda m, e, d: m, reduce_kind="mode",
                 num_message_dims=1)
  with pytest.raises(ValueError):
    cdlp_program().reduce_fn()
  n, src, dst = _rmat(6, 0)
  g = G.build_coo(src, dst, n=n)
  floats = jnp.zeros((n,), jnp.float32)
  with pytest.raises(TypeError, match="integer"):
    spmv(g, floats, jnp.ones((n,), bool), floats, cdlp_program(),
         backend=B.Plan("coo"))


def _containers():
  n, src, dst = _rmat(7, 0)
  return n, {"dense": G.build_dense(src, dst, n=n),
             "ell": G.build_ell(src, dst, n=n),
             "pallas": G.build_ell(src, dst, n=n),
             "coo_tiled": G.build_coo(src, dst, n=n)}


@pytest.mark.parametrize("backend", ["dense", "ell", "pallas", "coo_tiled"])
def test_explicit_plan_refuses_mode(backend):
  """The backend refuses the mode program, and the explicit plan raises:
  no other backend takes the call in its place."""
  n, graphs = _containers()
  g = graphs[backend]
  labels = jnp.arange(n, dtype=jnp.int32)
  assert not B.get_backend(backend).supports(g, labels, labels,
                                             cdlp_program())
  with pytest.raises(TypeError, match="mode"):
    cdlp(g, 1, backend=B.Plan(backend))


@pytest.mark.parametrize("backend", ["ell", "dense"])
def test_auto_plan_finds_no_backend_for_mode(backend):
  _, graphs = _containers()
  with pytest.raises(TypeError):
    cdlp(graphs[backend], 1)


def test_explicit_plan_fallback_kept_for_monoids():
  """A monoid program under a plan that cannot run it still falls back to
  the container's backend, as before."""
  n, graphs = _containers()
  prog = pagerank_program()
  msg = jnp.ones((n,), jnp.float32)
  impl = B.resolve(B.Plan("ell"), graphs["coo_tiled"], msg, msg, prog)
  assert impl.name in ("coo", "coo_tiled")


@pytest.mark.parametrize("runner", ["run_graph_program_2d",
                                    "run_graph_program_2d_batched"])
def test_2d_runners_refuse_mode(runner):
  n, src, dst = _rmat(6, 0)
  mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                           ("data", "model"))
  g = D.partition_2d(src, dst, n=n, R=1, C=1, mesh=mesh)
  labels = jnp.arange(g.n_pad, dtype=jnp.int32)
  active = jnp.ones((g.n_pad,), bool)
  if runner.endswith("batched"):
    labels, active = labels[:, None], active[:, None]
  with pytest.raises(ValueError, match="mode"):
    getattr(D, runner)(g, cdlp_program(), labels, active, mesh, max_iters=1)
