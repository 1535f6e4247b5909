#!/usr/bin/env python3
"""Chip smoke test: GraphMat's main path on one TPU at Graph500 scale 22.

Drives ``algos`` → engine → plan/backends → ``GraphQueryServer`` through the
public entry points on a Graph500 Kronecker graph (A=.57, B=C=.19, edge
factor 16; self loops removed, deduplicated, vertices shuffled; SSSP edge
weights uniform in [1, 2)) made from ``--seed``, and checks every result
against ``scipy.sparse`` / ``scipy.sparse.csgraph``.

  python chip_smoke.py                 # one chip, scale 22
  python chip_smoke.py --four-chips    # 2x2 mesh: 2-D runner vs one device

Without a TPU it exits non-zero and prints no result.  The last line of
stdout is one JSON object, ``{"ok": true, "device": {...}}``; the timings on
earlier lines are smoke timings, not a benchmark.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from typing import Callable, Dict, List, Sequence

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")  # libtpu logs nowhere else

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import scipy  # noqa: E402
import scipy.sparse as sp  # noqa: E402
from scipy.sparse import csgraph  # noqa: E402

from repro.algos import bfs, pagerank, sssp  # noqa: E402
from repro.algos.bfs import UNREACHED, bfs_program  # noqa: E402
from repro.algos.pagerank import pagerank_program  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import graph as G  # noqa: E402
from repro.core.backends import Plan  # noqa: E402
from repro.core.distributed import (pad_vertex_tree, partition_2d,  # noqa: E402
                                    run_graph_program_2d)
from repro.core.engine import run_graph_program  # noqa: E402
from repro.graphs import (dedupe_edges, remove_self_loops,  # noqa: E402
                          rmat_edges, shuffle_vertices)
from repro.graphs.rmat import RMAT_PRBFS  # noqa: E402
from repro.service import BfsFamily, GraphQueryServer, QuerySpec  # noqa: E402

EDGE_FACTOR = 16
PR_ITERS = 10
NUM_ROOTS = 16          # server queries; the first BFS_ROOTS also run alone
BFS_ROOTS = 4
NUM_SLOTS = 8
# float32 engine sums vs the float64 reference.
PR_RTOL, PR_ATOL = 1e-4, 1e-6
SSSP_RTOL = 1e-5
COO_PLAN = Plan(backend="coo")
PALLAS_PLAN = Plan(backend="pallas")
KERNEL_MARK = "tpu_custom_call"


def report(record: dict) -> dict:
  """Print one phase record (a smoke timing, not a benchmark)."""
  print("smoke " + json.dumps(record), flush=True)
  return record


@dataclasses.dataclass
class Graphs:
  n: int
  src: np.ndarray
  dst: np.ndarray
  w: np.ndarray
  coo: G.CooGraph
  ell: G.EllGraph


def make_edges(scale: int, seed: int):
  """Graph500 Kronecker edges, cleaned and relabelled, plus SSSP weights."""
  src, dst = rmat_edges(scale, EDGE_FACTOR, RMAT_PRBFS, seed=seed)
  src, dst = remove_self_loops(src, dst)
  src, dst = dedupe_edges(src, dst)
  n = 1 << scale
  src, dst, _ = shuffle_vertices(src, dst, n, seed=seed + 1)
  w = np.random.default_rng(seed + 2).uniform(1.0, 2.0, src.size)
  return n, src, dst, w.astype(np.float32)


def build_graphs(scale: int, seed: int) -> Graphs:
  t0 = time.perf_counter()
  n, src, dst, w = make_edges(scale, seed)
  t1 = time.perf_counter()
  coo = G.build_coo(src, dst, w, n=n)
  ell = G.build_ell(src, dst, w, n=n)
  jax.block_until_ready((coo, ell))
  t2 = time.perf_counter()
  report({"phase": "build", "n": n, "edges": int(src.size),
          "generate_s": t1 - t0, "build_s": t2 - t1,
          "device_bytes": sum(x.nbytes for x in
                              jax.tree_util.tree_leaves((coo, ell))),
          "ell_width": ell.width, "ell_n_pad": ell.n_pad,
          "ell_slots_in_extents": sum(ell.slot_rows),
          "ell_split_rows": int(ell.split_rows.shape[0])})
  return Graphs(n, src, dst, w, coo, ell)


class Reference:
  """scipy answers on the same edges, independent of the code under test."""

  def __init__(self, n: int, src, dst, w):
    self.n = n
    self.fwd = sp.csr_matrix((w.astype(np.float64), (src, dst)), shape=(n, n))
    self.pull = sp.csr_matrix((np.ones(src.size), (dst, src)), shape=(n, n))
    self.out_deg = np.bincount(src, minlength=n)
    self.in_deg = np.bincount(dst, minlength=n)

  def bfs(self, root: int) -> np.ndarray:
    """Hop counts along directed edges (UNREACHED where unreachable)."""
    order, pred = csgraph.breadth_first_order(
        self.fwd, root, directed=True, return_predecessors=True)
    dist = np.full(self.n, int(UNREACHED), np.int32)
    # FIFO order: each level is a contiguous run, and the order positions of
    # the parents never decrease along it.
    pos = np.empty(self.n, np.int64)
    pos[order] = np.arange(order.size)
    parent_pos = pos[pred[order[1:]]]
    lo, hi, level = 0, 1, 0
    while lo < order.size:
      dist[order[lo:hi]] = level
      lo, hi, level = hi, 1 + int(np.searchsorted(parent_pos, hi)), level + 1
    return dist

  def sssp(self, root: int) -> np.ndarray:
    return csgraph.dijkstra(self.fwd, directed=True, indices=root)

  def pagerank(self, iters: int, r: float = 0.15) -> np.ndarray:
    """Fixed sweeps; vertices without in-edges keep their rank (1.0)."""
    inv = 1.0 / np.maximum(self.out_deg, 1)
    recv = self.in_deg > 0
    rank = np.ones(self.n)
    for _ in range(iters):
      rank = np.where(recv, r + (1.0 - r) * (self.pull @ (rank * inv)), rank)
    return rank


def pick_roots(ref: Reference, seed: int) -> List[int]:
  """Distinct roots with out-degree > 0 (Graph500's root rule)."""
  cand = np.flatnonzero(ref.out_deg > 0)
  rng = np.random.default_rng(seed + 3)
  return [int(v) for v in rng.choice(cand, NUM_ROOTS, replace=False)]


def compiled(fn: Callable, *args):
  """AOT-compile ``fn`` for ``args``: (executable, seconds, kernel in HLO)."""
  t0 = time.perf_counter()
  exe = jax.jit(fn).lower(*args).compile()
  return exe, time.perf_counter() - t0, KERNEL_MARK in exe.as_text()


def run_algorithms(fmt: str, graph, plan: Plan, ref: Reference,
                   roots: Sequence[int], bfs_ref: Dict[int, np.ndarray],
                   sssp_ref: np.ndarray, pr_ref: np.ndarray) -> List[dict]:
  """PageRank, BFS from BFS_ROOTS roots and SSSP from roots[0] on ``graph``,
  each checked against its reference (raises on any mismatch)."""
  n = ref.n
  out_deg = jnp.asarray(ref.out_deg.astype(np.float32))
  records = []

  exe, c_s, kern = compiled(
      lambda g, d: pagerank(g, d, num_iters=PR_ITERS, backend=plan),
      graph, out_deg)
  t0 = time.perf_counter()
  ranks = np.asarray(exe(graph, out_deg))
  wall = time.perf_counter() - t0
  np.testing.assert_allclose(ranks, pr_ref, rtol=PR_RTOL, atol=PR_ATOL)
  records.append(report({
      "phase": f"{fmt}/pagerank", "plan": plan.backend, "compile_s": c_s,
      "wall_s": wall, "kernel": kern, "iters": PR_ITERS,
      "max_rel_err": float(np.max(np.abs(ranks - pr_ref) / pr_ref))}))

  exe, c_s, kern = compiled(
      lambda g, r: bfs(g, r, n, backend=plan), graph, jnp.int32(0))
  t0 = time.perf_counter()
  for root in roots[:BFS_ROOTS]:
    np.testing.assert_array_equal(np.asarray(exe(graph, jnp.int32(root))),
                                  bfs_ref[root])
  records.append(report({
      "phase": f"{fmt}/bfs", "plan": plan.backend, "compile_s": c_s,
      "wall_s": time.perf_counter() - t0, "kernel": kern,
      "roots": list(roots[:BFS_ROOTS]), "exact": True}))

  exe, c_s, kern = compiled(
      lambda g, r: sssp(g, r, n, backend=plan), graph, jnp.int32(0))
  t0 = time.perf_counter()
  dist = np.asarray(exe(graph, jnp.int32(roots[0])))
  wall = time.perf_counter() - t0
  np.testing.assert_allclose(dist, sssp_ref, rtol=SSSP_RTOL)
  finite = np.isfinite(sssp_ref)
  records.append(report({
      "phase": f"{fmt}/sssp", "plan": plan.backend, "compile_s": c_s,
      "wall_s": wall, "kernel": kern, "root": roots[0],
      "reached": int(finite.sum()),
      "max_rel_err": float(np.max(np.abs(dist[finite] - sssp_ref[finite])
                                  / np.maximum(sssp_ref[finite], 1.0)))}))
  return records


def run_server(fmt: str, graph, n: int, plan, roots: Sequence[int],
               bfs_ref: Dict[int, np.ndarray]) -> dict:
  """Serve NUM_ROOTS BFS queries on NUM_SLOTS slots; check every answer."""
  server = GraphQueryServer(graph, BfsFamily(n), num_slots=NUM_SLOTS,
                            backend=plan)
  t0 = time.perf_counter()
  qids = server.submit_many([QuerySpec("bfs", r) for r in roots])
  server.step_round()
  first = time.perf_counter() - t0
  server.drain()
  wall = time.perf_counter() - t0
  for qid, root in zip(qids, roots):
    np.testing.assert_array_equal(server.result(qid), bfs_ref[root])
  stats = server.stats()["counters"]
  server.close()
  return report({
      "phase": f"{fmt}/server", "plan": server.plan.backend,
      "first_round_s": first, "wall_s": wall, "queries": len(qids),
      "slots": NUM_SLOTS, "rounds": stats.get("rounds"),
      "supersteps": stats.get("supersteps"), "exact": True})


def one_chip_phases(scale: int, seed: int) -> List[dict]:
  """Every one-chip phase in order; raises on the first failure."""
  g = build_graphs(scale, seed)
  t0 = time.perf_counter()
  ref = Reference(g.n, g.src, g.dst, g.w)
  roots = pick_roots(ref, seed)
  bfs_ref = {r: ref.bfs(r) for r in roots}
  sssp_ref = ref.sssp(roots[0])
  pr_ref = ref.pagerank(PR_ITERS)
  records = [report({"phase": "reference", "wall_s": time.perf_counter() - t0,
                     "library": f"scipy {scipy.__version__}"})]
  for fmt, graph, plan in (("coo", g.coo, COO_PLAN),
                           ("ell", g.ell, PALLAS_PLAN)):
    records += run_algorithms(fmt, graph, plan, ref, roots, bfs_ref,
                              sssp_ref, pr_ref)
  for fmt, graph, plan in (("coo", g.coo, "auto"),
                           ("ell", g.ell, PALLAS_PLAN)):
    records.append(run_server(fmt, graph, g.n, plan, roots, bfs_ref))
  return records


def four_chip_phases(scale: int, seed: int) -> List[dict]:
  """2-D runner on a 2x2 mesh vs the single-device engine on device 0."""
  devices = jax.devices()[:4]
  assert len(devices) == 4, f"--four-chips needs 4 devices, have {devices}"
  mesh = jax.make_mesh((2, 2), ("data", "model"), devices=devices,
                       axis_types=(jax.sharding.AxisType.Auto,) * 2)
  t0 = time.perf_counter()
  n, src, dst, w = make_edges(scale, seed)
  dg = partition_2d(src, dst, w, n=n, R=2, C=2, mesh=mesh)
  coo = G.build_coo(src, dst, w, n=n)
  jax.block_until_ready((dg, coo))
  report({"phase": "build", "n": n, "edges": int(src.size),
          "build_s": time.perf_counter() - t0,
          "block_capacity": int(dg.src.shape[-1]),
          "bytes_per_device": [int(s.data.nbytes) for s in
                               dg.src.addressable_shards]})
  rows = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("data"))
  out_deg = np.bincount(src, minlength=n).astype(np.float32)
  root = int(np.flatnonzero(out_deg > 0)[0])
  cases = {
      "pagerank": (pagerank_program(), PR_ITERS,
                   {"rank": np.ones(n, np.float32), "deg": out_deg},
                   np.ones(n, bool), 0.0),
      "bfs": (bfs_program(), 0x7FFFFFF0,
              np.where(np.arange(n) == root, 0, int(UNREACHED)
                       ).astype(np.int32),
              np.arange(n) == root, int(UNREACHED)),
  }
  records = []
  for name, (prog, iters, prop, active, fill) in cases.items():
    prop_p = jax.device_put(pad_vertex_tree(prop, n, dg.n_pad, fill), rows)
    act_p = jax.device_put(pad_vertex_tree(active, n, dg.n_pad, False), rows)
    t0 = time.perf_counter()
    with jax.set_mesh(mesh):
      fin = run_graph_program_2d(dg, prog, prop_p, act_p, mesh,
                                 max_iters=iters)
    got = jax.tree_util.tree_map(lambda x: np.asarray(x)[:n], fin.prop)
    t_2d = time.perf_counter() - t0
    t0 = time.perf_counter()
    local = run_graph_program(coo, prog, prop, jnp.asarray(active),
                              max_iters=iters, backend=COO_PLAN)
    want = jax.tree_util.tree_map(np.asarray, local.prop)
    t_1 = time.perf_counter() - t0
    if name == "bfs":
      np.testing.assert_array_equal(got, want)
      err = 0.0
    else:
      np.testing.assert_allclose(got["rank"], want["rank"], rtol=PR_RTOL,
                                 atol=PR_ATOL)
      err = float(np.max(np.abs(got["rank"] - want["rank"]) / want["rank"]))
    records.append(report({
        "phase": f"2d/{name}", "mesh": "2x2", "wall_2d_s": t_2d,
        "wall_1dev_s": t_1, "supersteps_2d": int(fin.iteration),
        "supersteps_1dev": int(local.iteration), "max_rel_err": err,
        "note": "wall times include compilation"}))
  return records


def main(argv=None) -> int:
  ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
  ap.add_argument("--scale", type=int, default=22,
                  help="Graph500 scale: 2^scale vertices (default 22)")
  ap.add_argument("--seed", type=int, default=0)
  ap.add_argument("--four-chips", action="store_true",
                  help="run only the 2-D runner on a 2x2 mesh vs one device")
  args = ap.parse_args(argv)

  devices = jax.devices()
  dev = devices[0]
  print(f"jax {jax.__version__}; device_kind {dev.device_kind}; "
        f"platform {dev.platform}; count {len(devices)}", flush=True)
  if dev.platform != "tpu":
    print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
          file=sys.stderr)
    return 1
  print(f"compile cache: {enable_compile_cache()}", flush=True)
  print("timings below are smoke timings, not a benchmark", flush=True)

  if args.four_chips:
    four_chip_phases(args.scale, args.seed)
  else:
    records = one_chip_phases(args.scale, args.seed)
    missing = [r["phase"] for r in records
               if r["phase"].startswith("ell/") and "kernel" in r
               and not r["kernel"]]
    assert not missing, f"no compiled Pallas kernel in {missing}"
  stats = dev.memory_stats() or {}
  print(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}", flush=True)
  print(json.dumps({"ok": True, "device": {
      "platform": dev.platform, "kind": dev.device_kind,
      "count": len(devices)}}))
  return 0


if __name__ == "__main__":
  sys.exit(main())
