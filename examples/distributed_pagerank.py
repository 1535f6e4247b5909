"""Distributed GraphMat: PageRank on a 2x2 device mesh.

Shows the production path: 2-D partitioned graph, shard_map generalized
SpMV, semiring-aware cross-device reduction — the CombBLAS-style layout
with GraphMat's extended operators (DESIGN.md §4).

Uses the first four devices JAX has: four chips of a TPU host, or on the
CPU four virtual host devices (set below unless XLA_FLAGS is already set).

  PYTHONPATH=src python examples/distributed_pagerank.py
"""

import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import jax
import jax.numpy as jnp
import numpy as np

from repro.algos.pagerank import delta_pagerank_program
from repro.core import distributed as D
from repro.graphs import (dedupe_edges, remove_self_loops, rmat_edges,
                          shuffle_vertices)


def main():
  scale = 12
  src, dst = rmat_edges(scale, 8, seed=21)
  src, dst = remove_self_loops(src, dst)
  src, dst = dedupe_edges(src, dst)
  n = 1 << scale
  # Load-balance shuffle (the paper's over-partitioning analogue).
  src, dst, perm = shuffle_vertices(src, dst, n, seed=3)

  mesh = jax.make_mesh((2, 2), ("data", "model"),
                       devices=jax.devices()[:4],
                       axis_types=(jax.sharding.AxisType.Auto,) * 2)
  dg = D.partition_2d(src, dst, None, n=n, R=2, C=2, mesh=mesh)
  print(f"mesh 2×2, n={n} padded to {dg.n_pad}, "
        f"block capacity {dg.src.shape[-1]} edges")

  out_deg = np.bincount(src, minlength=dg.n_pad).astype(np.float32)
  r = 0.15
  prog = delta_pagerank_program(r=r, tol=1e-6)
  prop = {"rank": jnp.full((dg.n_pad,), r, jnp.float32),
          "delta": jnp.full((dg.n_pad,), r, jnp.float32),
          "deg": jnp.asarray(out_deg)}
  active = jnp.ones((dg.n_pad,), bool)

  with jax.set_mesh(mesh):
    final = D.run_graph_program_2d(dg, prog, prop, active, mesh,
                                   max_iters=50)
  ranks = np.asarray(final.prop["rank"])[:n]
  top = np.argsort(-ranks)[:5]
  print(f"stopped after {int(final.iteration)} supersteps with "
        f"{int(final.num_active)} vertices still above the tolerance")
  # shuffle_vertices relabelled v as perm[v]: map back to original ids.
  print("top-5 (original ids):", np.argsort(perm)[top].tolist())


if __name__ == "__main__":
  main()
