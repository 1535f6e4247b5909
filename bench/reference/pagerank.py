"""PageRank's plain reference, its control and its comparison.

The reference copies the ``Reference.pagerank`` of the repository's
``chip_smoke.py``: scipy in float64 on the host, built from the arcs the
benchmark generated.  The control runs the same equations with every rank
stored in bfloat16 (sums in float32), one precision below the
configurations' float32.  Nothing here imports the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp

NUMBERS = ("rank_rel_err",)
LOW = jnp.bfloat16


def _sweeps(config):
  pr = config["pagerank"]
  return int(pr["sweeps"]), float(pr["r"])


def reference(arcs, config, keys):
  """GraphMat's fixed-sweep PageRank, one answer per key (all alike): rank
  starts at 1, each sweep sets ``r + (1 - r) * sum(rank[u] / out_deg[u])``
  over in-arcs; vertices without in-arcs keep their rank."""
  iters, r = _sweeps(config)
  n = arcs.n
  pull = sp.csr_matrix((np.ones(arcs.num_arcs), (arcs.dst, arcs.src)),
                       shape=(n, n))
  inv = 1.0 / np.maximum(np.bincount(arcs.src, minlength=n), 1)
  recv = np.bincount(arcs.dst, minlength=n) > 0
  rank = np.ones(n)
  for _ in range(iters):
    rank = np.where(recv, r + (1.0 - r) * (pull @ (rank * inv)), rank)
  return [rank for _ in keys]


@functools.partial(jax.jit, static_argnames=("n", "iters", "r"))
def _low(src, dst, *, n: int, iters: int, r: float):
  ones = jnp.ones(src.shape, jnp.float32)
  out_deg = jax.ops.segment_sum(ones, src, n)
  recv = jax.ops.segment_sum(ones, dst, n) > 0

  def sweep(_, rank):
    msg = (rank.astype(jnp.float32) / jnp.maximum(out_deg, 1.0)).astype(LOW)
    acc = jax.ops.segment_sum(msg[src].astype(jnp.float32), dst, n)
    return jnp.where(recv, r + (1.0 - r) * acc,
                     rank.astype(jnp.float32)).astype(LOW)

  return jax.lax.fori_loop(0, iters, sweep, jnp.ones((n,), LOW))


def control(arcs, config, keys):
  iters, r = _sweeps(config)
  out = _low(jnp.asarray(arcs.src), jnp.asarray(arcs.dst), n=arcs.n,
             iters=iters, r=r)
  rank = np.asarray(out.astype(jnp.float32))
  return [rank for _ in keys]


def numbers(pairs, n: int, missing: int = 0):
  """``rank_rel_err``: largest |got - ref| / ref over vertices and answers
  (every reference rank is at least r > 0).  A missing answer fails."""
  worst = float("inf") if missing else 0.0
  for got, ref in pairs:
    err = np.abs(np.asarray(got, np.float64) - ref) / ref
    if not np.all(np.isfinite(err)):
      return {"rank_rel_err": float("inf")}
    worst = max(worst, float(np.max(err)) if err.size else 0.0)
  return {"rank_rel_err": worst}
