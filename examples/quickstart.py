"""Quickstart: write a vertex program, run it on an RMAT graph.

This is the paper's SSSP appendix translated to the JAX GraphMat API —
compare with the C++ listing in the paper: the five user hooks are the same.

  PYTHONPATH=src python examples/quickstart.py
"""

import jax.numpy as jnp
import numpy as np

from repro.core import build_ell, build_coo, run_graph_program
from repro.core.vertex_program import GraphProgram
from repro.graphs import dedupe_edges, remove_self_loops, rmat_edges


def main():
  # --- build a graph (Graph500 RMAT, paper §5.1) ------------------------
  scale = 12
  src, dst = rmat_edges(scale, edge_factor=8, seed=42)
  src, dst = remove_self_loops(src, dst)
  src, dst = dedupe_edges(src, dst)
  n = 1 << scale
  rng = np.random.default_rng(0)
  w = rng.uniform(0.1, 2.0, len(src)).astype(np.float32)
  graph = build_ell(src, dst, w, n=n)   # degree-sorted ELL (hubs split into rows)

  # --- the vertex program (paper appendix, SSSP) ------------------------
  sssp = GraphProgram(
      # PROCESS_MESSAGE: distance-so-far + edge weight
      process_message=lambda msg, edge, dst_prop: msg + edge,
      # REDUCE: min  (declared as a kind so backends can use fast paths)
      reduce_kind="min",
      # SEND_MESSAGE: the default — message = vertex property
      # APPLY: keep the shorter distance
      apply=lambda reduced, old: jnp.minimum(reduced, old),
      process_reads_dst=False,
      name="sssp")

  # --- run to convergence ------------------------------------------------
  source = 6  # the paper uses vertex 6 in its example
  dist0 = jnp.full((n,), jnp.inf, jnp.float32).at[source].set(0.0)
  active0 = jnp.zeros((n,), bool).at[source].set(True)
  final = run_graph_program(graph, sssp, dist0, active0)

  reached = int(jnp.sum(jnp.isfinite(final.prop)))
  print(f"SSSP from vertex {source}: converged in {int(final.iteration)} "
        f"supersteps, reached {reached}/{n} vertices")
  print("sample distances:", np.asarray(final.prop[:8]))


if __name__ == "__main__":
  main()
