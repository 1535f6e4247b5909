"""Native hand-optimized jnp baselines — see package docstring."""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


@functools.partial(jax.jit, static_argnames=("n", "num_iters", "r"))
def native_pagerank(src: Array, dst: Array, out_deg: Array, n: int,
                    num_iters: int = 20, r: float = 0.15) -> Array:
  """Straight gather/segment-sum power iteration."""
  inv_deg = 1.0 / jnp.maximum(out_deg.astype(jnp.float32), 1.0)
  # GraphMat semantics (paper Alg. 2): APPLY only on message receivers —
  # zero-in-degree vertices keep their initial rank.
  recv = jnp.zeros((n,), bool).at[dst].set(True)

  def body(_, rank):
    contrib = (rank * inv_deg)[src]
    agg = jnp.zeros((n,), jnp.float32).at[dst].add(contrib)
    return jnp.where(recv, r + (1.0 - r) * agg, rank)

  return jax.lax.fori_loop(0, num_iters, body, jnp.ones((n,), jnp.float32))


@functools.partial(jax.jit, static_argnames=("n", "root", "max_iters"))
def native_bfs(src: Array, dst: Array, n: int, root: int,
               max_iters: int = 0x7FFFFFF0) -> Array:
  big = jnp.int32(0x7FFFFFF0)
  dist0 = jnp.full((n,), big, jnp.int32).at[root].set(0)

  def cond(s):
    it, dist, changed = s
    return jnp.logical_and(changed, it < max_iters)

  def body(s):
    it, dist, _ = s
    cand = jnp.where(dist[src] < big, dist[src] + 1, big)
    nd = dist.at[dst].min(cand)
    return it + 1, nd, jnp.any(nd != dist)

  _, dist, _ = jax.lax.while_loop(cond, body, (jnp.int32(0), dist0, True))
  return dist


@functools.partial(jax.jit, static_argnames=("n", "source", "max_iters"))
def native_sssp(src: Array, dst: Array, w: Array, n: int, source: int,
                max_iters: int = 0x7FFFFFF0) -> Array:
  inf = jnp.float32(jnp.inf)
  dist0 = jnp.full((n,), inf, jnp.float32).at[source].set(0.0)

  def cond(s):
    it, dist, changed = s
    return jnp.logical_and(changed, it < max_iters)

  def body(s):
    it, dist, _ = s
    nd = dist.at[dst].min(dist[src] + w)
    return it + 1, nd, jnp.any(nd != dist)

  _, dist, _ = jax.lax.while_loop(cond, body, (jnp.int32(0), dist0, True))
  return dist


@functools.partial(jax.jit, static_argnames=("n",))
def native_tc(src: Array, dst: Array, n: int) -> Array:
  """Bitmap intersection per DAG edge: Σ popcount(out(u) & out(v)).

  Requires deduped edges (``dag_orient`` guarantees it): then every
  (row, word, bit) scatter target is unique and ``at[].add`` is an exact
  bitwise OR (each bit is a distinct power of two added at most once).
  """
  nw = (n + 31) // 32
  w_idx = dst // 32
  b_val = (jnp.uint32(1) << (dst % 32).astype(jnp.uint32))
  bits = jnp.zeros((n, nw), jnp.uint32).at[src, w_idx].add(b_val)
  inter = jnp.bitwise_and(bits[src], bits[dst])
  return jnp.sum(jax.lax.population_count(inter).astype(jnp.int64))


@functools.partial(jax.jit, static_argnames=("n", "k", "num_iters",
                                             "gamma", "lam", "seed"))
def native_cf(users: Array, items_g: Array, ratings: Array, n: int, k: int,
              num_iters: int = 10, gamma: float = 5e-4, lam: float = 0.05,
              seed: int = 0) -> Array:
  """Two-phase GD sweeps with raw gathers + segment sums.

  ``items_g`` are item vertex ids already offset into [U, U+I)."""
  rng = jax.random.PRNGKey(seed)
  p0 = jax.random.uniform(rng, (n, k), jnp.float32, 0.0, 0.1)

  def half_step(p, src_v, dst_v):
    ps, pd = p[src_v], p[dst_v]
    err = ratings - jnp.sum(ps * pd, axis=-1)
    upd = jnp.zeros((n, k), jnp.float32).at[dst_v].add(err[:, None] * ps)
    recv = jnp.zeros((n,), bool).at[dst_v].set(True)
    return jnp.where(recv[:, None], p + gamma * (upd - lam * p), p)

  def body(_, p):
    p = half_step(p, items_g, users)   # users gather from items
    p = half_step(p, users, items_g)   # items gather from users
    return p

  return jax.lax.fori_loop(0, num_iters, body, p0)


def native_cdlp(src, dst, n: int, num_iters: int) -> np.ndarray:
  """Graphalytics CDLP in plain numpy on the host: labels start as the
  vertex ids; each iteration sorts the packed keys ``dst * n + label[src]``
  (int64), so that each destination's equal labels form runs, and gives a
  destination the label of its longest run, ties to the smallest; a vertex
  with no in-arc keeps its label.  Returns int32 labels [n]."""
  src = np.asarray(src, np.int64)
  dst = np.asarray(dst, np.int64)
  labels = np.arange(n, dtype=np.int64)
  if not src.size:
    return labels.astype(np.int32)
  for _ in range(num_iters):
    key = np.sort(dst * n + labels[src])
    starts = np.flatnonzero(np.r_[True, key[1:] != key[:-1]])
    counts = np.diff(np.r_[starts, key.size])
    run_dst, run_lab = key[starts] // n, key[starts] % n
    heads = np.flatnonzero(np.r_[True, run_dst[1:] != run_dst[:-1]])
    best = np.repeat(np.maximum.reduceat(counts, heads),
                     np.diff(np.r_[heads, counts.size]))
    # Runs of a destination come in increasing label order.
    won = np.minimum.reduceat(np.where(counts == best, run_lab, n), heads)
    labels[run_dst[heads]] = won
  return labels.astype(np.int32)
