"""End-to-end behaviour tests: engine semantics, formats, convergence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import graph as G
from repro.core.engine import run_graph_program
from repro.core.vertex_program import GraphProgram
import repro.core.spmv as spmv_mod


def sssp_prog():
  return GraphProgram(
      process_message=lambda m, e, d: m + e,
      reduce_kind="min",
      apply=lambda red, old: jnp.minimum(red, old),
      process_reads_dst=False, name="sssp")


def bellman_ford(n, src, dst, w, source):
  inf = np.float32(np.inf)
  d = np.full(n, inf, np.float32)
  d[source] = 0
  for _ in range(n):
    nd = d.copy()
    np.minimum.at(nd, dst, d[src] + w)
    if np.allclose(nd, d, equal_nan=True):
      break
    d = nd
  return d


@pytest.mark.parametrize("backend", ["coo", "ell", "pallas"])
def test_sssp_converges_to_bellman_ford(rmat_small, backend):
  n, src, dst, w = rmat_small
  g = (G.build_coo(src, dst, w, n=n) if backend == "coo"
       else G.build_ell(src, dst, w, n=n))
  dist0 = jnp.full((n,), jnp.inf, jnp.float32).at[0].set(0.0)
  act0 = jnp.zeros((n,), bool).at[0].set(True)
  out = run_graph_program(g, sssp_prog(), dist0, act0, max_iters=300,
                          backend=backend)
  oracle = bellman_ford(n, src, dst, w, 0)
  np.testing.assert_allclose(np.asarray(out.prop), oracle, rtol=1e-5)


def test_engine_terminates_on_empty_frontier(rmat_small):
  n, src, dst, w = rmat_small
  g = G.build_coo(src, dst, w, n=n)
  dist0 = jnp.full((n,), jnp.inf, jnp.float32).at[0].set(0.0)
  act0 = jnp.zeros((n,), bool).at[0].set(True)
  out = run_graph_program(g, sssp_prog(), dist0, act0, max_iters=10**6,
                          backend="coo")
  assert int(out.iteration) < 300          # converged, not max_iters
  assert int(out.num_active) == 0


def test_backends_agree_one_superstep(rmat_small):
  n, src, dst, w = rmat_small
  coo = G.build_coo(src, dst, w, n=n)
  ell = G.build_ell(src, dst, w, n=n, width=8)   # forces split rows
  adj_v, adj_s = G.dense_adjacency(src, dst, w, n=n)
  rng = np.random.default_rng(1)
  msg = jnp.asarray(rng.uniform(0, 5, n).astype(np.float32))
  act = jnp.asarray(rng.uniform(size=n) > 0.5)
  prog = sssp_prog()
  y_d, r_d = spmv_mod.spmv_dense(adj_v, adj_s, msg, act, msg, prog)
  y_c, r_c = spmv_mod.spmv_coo(coo, msg, act, msg, prog)
  y_e, r_e = spmv_mod.spmv_ell(ell, msg, act, msg, prog)
  np.testing.assert_array_equal(np.asarray(r_d), np.asarray(r_c))
  np.testing.assert_array_equal(np.asarray(r_d), np.asarray(r_e))
  np.testing.assert_allclose(np.asarray(y_d), np.asarray(y_c), rtol=1e-6)
  np.testing.assert_allclose(np.asarray(y_d), np.asarray(y_e), rtol=1e-6)


def test_ell_roundtrip(rmat_small):
  n, src, dst, w = rmat_small
  ell = G.build_ell(src, dst, w, n=n, width=8)
  s2, d2, w2 = G.coo_from_ell(ell)
  a = sorted(zip(src.tolist(), dst.tolist(), w.tolist()))
  b = sorted(zip(s2.tolist(), d2.tolist(), w2.tolist()))
  assert a == b


def test_generic_reduce_matches_fast_path(rmat_small):
  n, src, dst, w = rmat_small
  coo = G.build_coo(src, dst, w, n=n)
  rng = np.random.default_rng(2)
  msg = jnp.asarray(rng.uniform(0, 5, n).astype(np.float32))
  act = jnp.asarray(rng.uniform(size=n) > 0.3)
  fast = GraphProgram(process_message=lambda m, e, d: m * e,
                      reduce_kind="add", process_reads_dst=False)
  gen = GraphProgram(process_message=lambda m, e, d: m * e,
                     reduce_kind="generic",
                     reduce=lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                     reduce_identity=0.0, process_reads_dst=False)
  y1, _ = spmv_mod.spmv_coo(coo, msg, act, msg, fast)
  y2, _ = spmv_mod.spmv_coo(coo, msg, act, msg, gen)
  np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-5)
