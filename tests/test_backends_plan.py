"""Execution-plan layer: registry, Plan coercion, planner, conformance.

Covers the PR-9 acceptance criteria:

* string ``backend=`` and :class:`Plan` spellings produce bitwise-identical
  results (the coercion shim is a pure respelling);
* every registered backend agrees with the dense oracle on all five algo
  families (exact for min-monoid programs; tolerance for add-reduce, where
  XLA reassociates the dense reduction) and coo_tiled is bitwise equal to
  untiled COO;
* the planner picks different backends for skewed vs uniform graphs, and
  :meth:`Planner.autotune` memoizes by graph fingerprint;
* the registry is the extension point: a user-registered backend is
  resolvable by explicit plan.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.algos import (bfs, multi_bfs, pagerank, personalized_pagerank,
                         sssp)
from repro.core import graph as G
from repro.core import backends as B
from repro.core.backends import plan as plan_mod
from repro.core.backends.planner import Planner, compute_stats
from repro.core import semiring as sr
from repro.core.spmv import spmv, spmv_coo, spmv_coo_tiled, spmv_dense
from repro.algos.bfs import bfs_program
from repro.algos.pagerank import pagerank_program


def _random_graph(seed, n=96, e=500):
  # Deduped: the dense oracle stores one weight per (src, dst) pair, so
  # cross-container comparisons need multiplicity-free edge lists.
  from repro.graphs import dedupe_edges
  rng = np.random.default_rng(seed)
  src = rng.integers(0, n, e).astype(np.int32)
  dst = rng.integers(0, n, e).astype(np.int32)
  keep = src != dst
  src, dst = dedupe_edges(src[keep], dst[keep])
  w = rng.uniform(0.1, 2.0, src.size).astype(np.float32)
  return n, src, dst, w


def _skewed_graph(n=128, hub_edges=400, rest=100, seed=0):
  """Hub-dominated in-degree: most edges land on vertex 0."""
  rng = np.random.default_rng(seed)
  src = np.concatenate([rng.integers(1, n, hub_edges),
                        rng.integers(0, n, rest)]).astype(np.int32)
  dst = np.concatenate([np.zeros(hub_edges, np.int32),
                        rng.integers(0, n, rest).astype(np.int32)])
  keep = src != dst
  src, dst = src[keep], dst[keep]
  w = np.ones(src.size, np.float32)
  return n, src, dst, w


def _ring_graph(n=128):
  """Uniform in-degree 1 — zero skew."""
  src = np.arange(n, dtype=np.int32)
  dst = (src + 1) % n
  return n, src, dst, np.ones(n, np.float32)


def _build(container, src, dst, w, n):
  if container == "dense":
    return G.build_dense(src, dst, w, n=n)
  if container == "ell":
    return G.build_ell(src, dst, w, n=n)
  return G.build_coo(src, dst, w, n=n)


# -- coercion shim ------------------------------------------------------------


def test_as_plan_spellings():
  assert B.as_plan(None) is B.AUTO_PLAN
  p = B.Plan(backend="ell")
  assert B.as_plan(p) is p
  assert B.as_plan("auto") == B.AUTO_PLAN
  assert B.as_plan("coo") == B.Plan(backend="coo")
  with pytest.raises(ValueError, match="unknown backend"):
    B.as_plan("csr")
  with pytest.raises(TypeError):
    B.as_plan(42)


def test_string_coercion_warns_once():
  plan_mod._warned_string_coercion = False
  try:
    with warnings.catch_warnings(record=True) as rec:
      warnings.simplefilter("always")
      B.as_plan("coo")
      B.as_plan("ell")
    deps = [w for w in rec if issubclass(w.category, DeprecationWarning)]
    assert len(deps) == 1
    # "auto" is the documented default sentinel: never warns.
    plan_mod._warned_string_coercion = False
    with warnings.catch_warnings(record=True) as rec:
      warnings.simplefilter("always")
      B.as_plan("auto")
    assert not [w for w in rec if issubclass(w.category, DeprecationWarning)]
  finally:
    plan_mod._warned_string_coercion = True


def test_plan_validation():
  with pytest.raises(ValueError, match="direction"):
    B.Plan(direction="push")
  with pytest.raises(ValueError, match="num_tiles"):
    B.Plan(backend="coo_tiled", num_tiles=0)
  p = B.Plan(backend="pallas", block_rows=256, block_queries=8)
  assert p.kernel_kwargs() == {"block_rows": 256, "block_queries": 8}
  assert hash(p) == hash(B.Plan(backend="pallas", block_rows=256,
                                block_queries=8))


@pytest.mark.parametrize("name", ["coo", "ell", "dense"])
def test_string_and_plan_bitwise_identical(name):
  n, src, dst, w = _random_graph(0)
  impl = B.get_backend(name)
  g = _build(impl.container, src, dst, w, n)
  via_str = np.asarray(bfs(g, 0, n, backend=name))
  via_plan = np.asarray(bfs(g, 0, n, backend=B.Plan(backend=name)))
  np.testing.assert_array_equal(via_str, via_plan)
  out_deg = jnp.asarray(np.bincount(src, minlength=n).astype(np.float32))
  r_str = np.asarray(pagerank(g, out_deg, num_iters=8, backend=name))
  r_plan = np.asarray(pagerank(g, out_deg, num_iters=8,
                               backend=B.Plan(backend=name)))
  np.testing.assert_array_equal(r_str, r_plan)


# -- registry -----------------------------------------------------------------


def test_registry_lists_builtins():
  names = B.registered_backends()
  for expected in ("dense", "coo", "coo_tiled", "ell", "pallas"):
    assert expected in names
  # Priority-ordered: the dense oracle outranks everything.
  assert names[0] == "dense"


def test_registry_is_the_extension_point():
  calls = []

  class Spy(B.Backend):
    name = "spy_coo"
    container = "coo"
    priority = 1  # never auto-selected ahead of the builtins

    def supports(self, graph, msg, dst_prop, program):
      return isinstance(graph, G.CooGraph)

    def eligible(self, graph, msg, dst_prop, program):
      return False  # explicit-plan only

    def execute(self, graph, msg, active, dst_prop, program, plan,
                with_recv):
      calls.append(plan)
      return spmv_coo(graph, msg, active, dst_prop, program,
                      with_recv=with_recv)

  B.register(Spy())
  try:
    assert "spy_coo" in B.registered_backends()
    with pytest.raises(ValueError, match="already registered"):
      B.register(Spy())
    n, src, dst, w = _random_graph(1)
    g = G.build_coo(src, dst, w, n=n)
    d_spy = np.asarray(bfs(g, 0, n, backend=B.Plan(backend="spy_coo")))
    d_ref = np.asarray(bfs(g, 0, n, backend="coo"))
    np.testing.assert_array_equal(d_spy, d_ref)
    assert calls and all(p.backend == "spy_coo" for p in calls)
  finally:
    B.unregister("spy_coo")
  assert "spy_coo" not in B.registered_backends()


def test_unknown_explicit_plan_raises():
  n, src, dst, w = _random_graph(0)
  g = G.build_coo(src, dst, w, n=n)
  prog = bfs_program()
  msg = jnp.zeros((n,), jnp.int32)
  active = jnp.ones((n,), bool)
  with pytest.raises(KeyError, match="no backend"):
    spmv(g, msg, active, None, prog, backend=B.Plan(backend="nope"))


# -- the table of reduce kinds (core/semiring.py) -----------------------------


@pytest.mark.parametrize("kind", sorted(sr.MONOIDS))
def test_monoid_table_entry(kind):
  """Each built-in monoid, read from the table, gives the dense oracle's
  answer on the COO, jnp-ELL (split rows folded by ``combine``) and Pallas
  paths, and through the mesh collective of a 1×1 mesh; the kernel
  predicate follows the entry's ``pallas``."""
  from repro.core import distributed as D
  from repro.core.backends.pallas import kernel_takes
  from repro.core.vertex_program import GraphProgram
  from repro.kernels.ops import spmv_ell_pallas
  monoid = sr.MONOIDS[kind]
  n, src, dst, w = _random_graph(5)
  rng = np.random.default_rng(5)
  if monoid.identity(jnp.float32).dtype == jnp.bool_:
    msg = jnp.asarray(rng.uniform(size=n) < 0.5)
    process = lambda m, e, d: jnp.logical_and(m, e > 1.0)
  else:
    msg = jnp.asarray(rng.uniform(0.5, 2.0, n).astype(np.float32))
    process = lambda m, e, d: m * e
  prog = GraphProgram(process_message=process, reduce_kind=kind,
                      process_reads_dst=False, name=f"monoid_{kind}")
  act = jnp.asarray(rng.uniform(size=n) < 0.7)
  ell = G.build_ell(src, dst, w, n=n, width=4)
  assert ell.split_rows.shape[0] > 0     # the fold's combine is on the path
  mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                           ("data", "model"))
  paths = {
      "coo": spmv_coo(G.build_coo(src, dst, w, n=n), msg, act, msg, prog),
      "ell": B.get_backend("ell").execute(ell, msg, act, msg, prog,
                                          B.Plan("ell"), True),
      "2d": jax.jit(lambda g, m, a: D.spmv_2d(g, m, a, m, prog, mesh))(
          D.partition_2d(src, dst, w, n=n, R=1, C=1, mesh=mesh), msg, act),
  }
  assert kernel_takes(prog, msg, msg) == kernel_takes(prog) == monoid.pallas
  if monoid.pallas:
    paths["pallas"] = spmv_ell_pallas(ell, msg, act, msg, prog)
  else:
    assert B.resolve(B.AUTO_PLAN, ell, msg, msg, prog).name == "ell"
  adj_v, adj_s = G.dense_adjacency(src, dst, w, n=n)
  y_d, r_d = spmv_dense(adj_v, adj_s, msg, act, msg, prog)
  for path, (y, r) in paths.items():
    np.testing.assert_array_equal(np.asarray(r), np.asarray(r_d), path)
    if kind == "add":
      np.testing.assert_allclose(np.asarray(y), np.asarray(y_d), rtol=1e-5,
                                 err_msg=path)
    else:
      np.testing.assert_array_equal(np.asarray(y), np.asarray(y_d), path)


# -- cross-backend conformance (all registered × five families) ---------------

FAMILIES = ("bfs", "sssp", "pagerank", "multi_bfs", "personalized_pagerank")
# min-monoid programs are bitwise vs the dense oracle; add-reduce programs
# compare with tolerance (XLA reassociates the dense axis-reduce).
EXACT = ("bfs", "sssp", "multi_bfs")


def _run_family(family, g, n, src, backend):
  out_deg = jnp.asarray(np.bincount(src, minlength=n).astype(np.float32))
  if family == "bfs":
    return np.asarray(bfs(g, 0, n, backend=backend))
  if family == "sssp":
    d = np.asarray(sssp(g, 3, n, backend=backend))
    return np.nan_to_num(d, posinf=1e30)
  if family == "pagerank":
    return np.asarray(pagerank(g, out_deg, num_iters=10, backend=backend))
  if family == "multi_bfs":
    return np.asarray(
        multi_bfs(g, np.array([0, 7, 23], np.int32), n, backend=backend))
  return np.asarray(personalized_pagerank(
      g, out_deg, np.array([1, 9, 40], np.int32), tol=1e-7,
      backend=backend))


@pytest.mark.parametrize("family", FAMILIES)
@pytest.mark.parametrize("name", ["dense", "coo", "coo_tiled", "ell",
                                  "pallas"])
def test_backend_conformance(family, name):
  if name == "pallas" and family == "personalized_pagerank":
    pytest.skip("PPR's activate-driven frontier is served by the jnp ELL "
                "path (matches test_batched_engine convention)")
  n, src, dst, w = _random_graph(4)
  impl = B.get_backend(name)
  g = _build(impl.container, src, dst, w, n)
  dense_g = _build("dense", src, dst, w, n)
  got = _run_family(family, g, n, src, B.Plan(backend=name))
  ref = _run_family(family, dense_g, n, src, B.Plan(backend="dense"))
  if family in EXACT:
    np.testing.assert_array_equal(got, ref)
  else:
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=1e-6)


@pytest.mark.parametrize("family", FAMILIES)
@pytest.mark.parametrize("num_tiles", [1, 3, 8])
def test_tiled_coo_bitwise_equals_untiled(family, num_tiles):
  """Edge tiling is a pure scheduling change: bitwise-identical to the
  monolithic COO scatter (same per-destination accumulation order)."""
  n, src, dst, w = _random_graph(5)
  g = G.build_coo(src, dst, w, n=n)
  tiled = _run_family(family, g, n, src,
                      B.Plan(backend="coo_tiled", num_tiles=num_tiles))
  untiled = _run_family(family, g, n, src, B.Plan(backend="coo"))
  np.testing.assert_array_equal(tiled, untiled)


def test_tiled_coo_remainder_capacity():
  """Capacity not divisible by the tile count pads correctly."""
  n, src, dst, w = _random_graph(6, n=50, e=101)
  g = G.build_coo(src, dst, w, n=n)
  prog = bfs_program()
  msg = jnp.full((n,), 7, jnp.int32)
  active = jnp.ones((n,), bool)
  y_t, r_t = spmv_coo_tiled(g, msg, active, None, prog, num_tiles=7)
  y_u, r_u = spmv_coo(g, msg, active, None, prog)
  np.testing.assert_array_equal(np.asarray(y_t), np.asarray(y_u))
  np.testing.assert_array_equal(np.asarray(r_t), np.asarray(r_u))


def test_auto_never_picks_explicit_only_backends():
  """Structural auto-dispatch on a CooGraph stays on plain COO: coo_tiled
  is planner/explicit-plan territory (eligible() is False)."""
  n, src, dst, w = _random_graph(0)
  g = G.build_coo(src, dst, w, n=n)
  prog = bfs_program()
  msg = jnp.zeros((n,), jnp.int32)
  impl = B.resolve(B.AUTO_PLAN, g, msg, None, prog)
  assert impl.name == "coo"


# -- planner ------------------------------------------------------------------


def test_stats_skew_signal():
  n, src, dst, w = _skewed_graph()
  skewed = compute_stats(G.build_coo(src, dst, w, n=n))
  n2, src2, dst2, w2 = _ring_graph()
  uniform = compute_stats(G.build_coo(src2, dst2, w2, n=n2))
  assert skewed.hub_ratio > 10 * uniform.hub_ratio
  assert uniform.hub_ratio == pytest.approx(1.0)


def test_planner_skewed_vs_uniform_pick_different_backends():
  planner = Planner(tile_edges=64)  # small graphs → still multiple tiles
  prog = bfs_program()
  n, src, dst, w = _skewed_graph()
  skew_plan = planner.plan(G.build_coo(src, dst, w, n=n), prog)
  n2, src2, dst2, w2 = _ring_graph()
  ring_plan = planner.plan(G.build_coo(src2, dst2, w2, n=n2), prog)
  assert skew_plan.backend == "coo_tiled"
  assert skew_plan.num_tiles is not None and skew_plan.num_tiles > 1
  assert ring_plan.backend == "coo"
  assert skew_plan.backend != ring_plan.backend


def test_planner_dense_and_ell_containers():
  planner = Planner()
  n, src, dst, w = _random_graph(0)
  assert planner.plan(_build("dense", src, dst, w, n)).backend == "dense"
  ell_plan = planner.plan(_build("ell", src, dst, w, n), bfs_program())
  assert ell_plan.backend in ("pallas", "ell")
  # Generic-reduce programs can't use the kernel: ELL fallback.
  from repro.algos.triangle_count import bitmap_build_program
  assert planner.plan(_build("ell", src, dst, w, n),
                      bitmap_build_program()).backend == "ell"


def test_planner_rejects_traced_graphs():
  n, src, dst, w = _random_graph(0)
  g = G.build_coo(src, dst, w, n=n)
  planner = Planner()

  @jax.jit
  def traced(g):
    planner.plan(g)
    return jnp.zeros(())

  with pytest.raises(TypeError, match="concrete graph"):
    traced(g)


def test_autotune_memoizes_by_fingerprint():
  n, src, dst, w = _random_graph(7)
  g = G.build_coo(src, dst, w, n=n)
  # Same content, different arrays: the fingerprint (not object identity)
  # must key the cache.
  g2 = G.build_coo(src.copy(), dst.copy(), w.copy(), n=n)
  prog = bfs_program()
  prop0 = jnp.full((n,), 0x7FFFFFF0, jnp.int32).at[0].set(0)
  active0 = jnp.zeros((n,), bool).at[0].set(True)
  planner = Planner()
  cands = [B.Plan(backend="coo"),
           B.Plan(backend="coo_tiled", num_tiles=2)]
  p1 = planner.autotune(g, prog, prop0, active0, candidates=cands,
                        repeats=1)
  assert planner.cache.misses == 1 and planner.cache.hits == 0
  p2 = planner.autotune(g2, prog, prop0, active0, candidates=cands,
                        repeats=1)
  assert p2 == p1
  assert planner.cache.hits == 1 and len(planner.cache) == 1
  assert p1.backend in ("coo", "coo_tiled")


def test_autotune_survives_broken_candidates():
  """Candidates that cannot execute lose instead of raising, and the
  failure is recorded and warned about."""

  class Boom(B.Backend):
    name = "boom"
    container = "coo"
    priority = 0

    def supports(self, graph, msg, dst_prop, program):
      return True

    def eligible(self, graph, msg, dst_prop, program):
      return False

    def execute(self, graph, msg, active, dst_prop, program, plan,
                with_recv):
      raise RuntimeError("boom")

  B.register(Boom())
  try:
    n, src, dst, w = _random_graph(8)
    g = G.build_coo(src, dst, w, n=n)
    prog = bfs_program()
    prop0 = jnp.full((n,), 0x7FFFFFF0, jnp.int32).at[0].set(0)
    active0 = jnp.zeros((n,), bool).at[0].set(True)
    planner = Planner()
    cands = [B.Plan(backend="boom"), B.Plan(backend="coo")]
    with pytest.warns(RuntimeWarning, match="boom"):
      p = planner.autotune(g, prog, prop0, active0, candidates=cands,
                           repeats=1)
    assert p == B.Plan(backend="coo")
    # The failure is recorded with its exception, not swallowed.
    assert [f[0] for f in planner.failures] == [B.Plan(backend="boom")]
    assert "boom" in planner.failures[0][1]
  finally:
    B.unregister("boom")


def test_candidates_cover_tiling_sweep():
  planner = Planner(tile_edges=64)
  n, src, dst, w = _skewed_graph()
  g = G.build_coo(src, dst, w, n=n)
  cands = planner.candidates(g, bfs_program())
  names = [c.backend for c in cands]
  assert "coo" in names and "coo_tiled" in names
  tiles = sorted(c.num_tiles for c in cands if c.backend == "coo_tiled")
  assert len(tiles) >= 2  # sweeps more than one tile count


# -- server integration -------------------------------------------------------


def test_server_plans_and_replans_on_swap():
  from repro.service.scheduler import BfsFamily, GraphQueryServer, QuerySpec
  planner = Planner(tile_edges=64)
  n, src, dst, w = _skewed_graph()
  g_skew = G.build_coo(src, dst, w, n=n)
  n2, src2, dst2, w2 = _ring_graph()
  g_ring = G.build_coo(src2, dst2, w2, n=n2)

  srv = GraphQueryServer(g_skew, BfsFamily(n), num_slots=2, planner=planner)
  assert srv.plan.backend == "coo_tiled"
  fp_before = srv.fingerprint
  qid = srv.submit(QuerySpec("bfs", 5))
  srv.drain()
  assert np.asarray(srv.result(qid))[5] == 0

  new_plan = srv.swap_graph(g_ring)
  assert new_plan.backend == "coo"          # re-planned for the new graph
  assert srv.fingerprint != fp_before
  qid2 = srv.submit(QuerySpec("bfs", 5))
  srv.drain()
  got = np.asarray(srv.result(qid2))
  assert got[5] == 0 and got[(5 + 1) % n2] == 1  # ring distances


def test_server_swap_requires_idle():
  from repro.service.scheduler import BfsFamily, GraphQueryServer, QuerySpec
  n, src, dst, w = _random_graph(0)
  g = G.build_coo(src, dst, w, n=n)
  srv = GraphQueryServer(g, BfsFamily(n), num_slots=2)
  srv.submit(QuerySpec("bfs", 1))
  with pytest.raises(RuntimeError, match="idle"):
    srv.swap_graph(g)
  srv.drain()
  srv.swap_graph(g)  # idle now: fine


def test_server_explicit_plan_is_respected():
  from repro.service.scheduler import BfsFamily, GraphQueryServer, QuerySpec
  n, src, dst, w = _random_graph(0)
  g = G.build_coo(src, dst, w, n=n)
  plan = B.Plan(backend="coo_tiled", num_tiles=4)
  srv = GraphQueryServer(g, BfsFamily(n), num_slots=2, backend=plan)
  assert srv.plan is plan
  qid = srv.submit(QuerySpec("bfs", 0))
  srv.drain()
  ref = np.asarray(bfs(g, 0, n, backend="coo"))
  np.testing.assert_array_equal(np.asarray(srv.result(qid)), ref)
