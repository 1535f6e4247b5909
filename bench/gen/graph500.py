"""Graph500 Kronecker graph, generated and cleaned on the device.

Graph500 specification v3.0, section 3: ``2^scale`` vertices and
``edgefactor * 2^scale`` edge tuples, each placed by ``scale`` independent
choices of a quadrant of the adjacency matrix with probabilities A, B, C and
D = 1 - A - B - C (the reference generator's two-draw form: the row bit is
set with probability 1 - (A + B), then the column bit with C / (C + D) or
B / (A + B)).  No noise is added to the quadrant probabilities.

The edge tuples are treated as undirected: self loops are removed,
duplicates (in either orientation) are merged, each remaining edge gets a
weight uniform in [0, 1) (the SSSP kernel's weights, section 6), the vertex
labels are permuted, and both arcs of every edge are returned with the same
weight.  Everything up to the final transfer runs in jitted calls on the
device, from one ``jax.random`` key made of the seed; only the label
permutation is drawn on the host, from the same seed.

A configuration may fix the graph, as LDBC Graphalytics ships its graph500
data sets as fixed files: with ``graph_seed`` the edges and weights come
from that seed and only the label permutation from the run's, so every run
seed gets the same weighted graph under other labels.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Arcs:
  """Both arcs of every undirected edge, on the host."""

  n: int
  src: np.ndarray   # int32[m]
  dst: np.ndarray   # int32[m]
  w: np.ndarray     # float32[m], w of (u, v) == w of (v, u)
  graph_seed: int   # the seed the edges and weights came from
  perm: np.ndarray  # int32[n]: perm[v] is the label vertex v is given

  @property
  def num_arcs(self) -> int:
    return int(self.src.size)


def seed_key(seed: int) -> jax.Array:
  """A PRNG key from any whole number below 2**64 (two 32-bit words)."""
  seed = int(seed)
  if not 0 <= seed < 1 << 64:
    raise ValueError(f"seed {seed} is outside [0, 2**64)")
  return jax.random.key_data(jax.random.wrap_key_data(
      jnp.asarray([seed >> 32, seed & 0xFFFFFFFF], jnp.uint32)))


@functools.partial(jax.jit, static_argnames=("scale", "edgefactor", "abc"))
def kronecker_tuples(key, *, scale: int, edgefactor: int, abc):
  """Raw edge tuples ``(row, col)``, int32 ``[edgefactor * 2^scale]`` each."""
  a, b, c = abc
  ab = a + b
  c_norm = c / (1.0 - ab)
  a_norm = a / ab
  m = edgefactor << scale
  keys = jax.random.split(jax.random.wrap_key_data(key), scale)

  def level(i, rc):
    row, col = rc
    k_row, k_col = jax.random.split(keys[i])
    row_bit = jax.random.uniform(k_row, (m,)) > ab
    col_bit = jax.random.uniform(k_col, (m,)) > jnp.where(row_bit, c_norm,
                                                          a_norm)
    return (row | (row_bit.astype(jnp.int32) << i),
            col | (col_bit.astype(jnp.int32) << i))

  zeros = jnp.zeros((m,), jnp.int32)
  row, col = jax.lax.fori_loop(0, scale, level, (zeros, zeros))
  return row, col


@functools.partial(jax.jit, static_argnames=("n",))
def _clean(key, row, col, perm, *, n: int):
  """Undirected, loop-free, duplicate-free edges with weights and labels
  relabelled by ``perm``; the ``count`` real ones come first."""
  lo = jnp.minimum(row, col)
  hi = jnp.maximum(row, col)
  loop = lo == hi
  lo = jnp.where(loop, n, lo)        # loops sort last and are dropped
  hi = jnp.where(loop, n, hi)
  lo, hi = jax.lax.sort((lo, hi), num_keys=2)
  first = jnp.concatenate([jnp.ones((1,), bool),
                           (lo[1:] != lo[:-1]) | (hi[1:] != hi[:-1])])
  keep = first & (lo < n)
  # Compact the kept edges to the front, in their sorted order.
  pos = jnp.where(keep, jnp.cumsum(keep) - 1, lo.shape[0])
  lo = jnp.zeros_like(lo).at[pos].set(lo, mode="drop")
  hi = jnp.zeros_like(hi).at[pos].set(hi, mode="drop")
  w = jax.random.uniform(jax.random.wrap_key_data(key), lo.shape, jnp.float32)
  return perm[lo], perm[hi], w, jnp.sum(keep)


def generate(seed: int, *, scale: int, edgefactor: int = 16,
             abc=(0.57, 0.19, 0.19), graph_seed=None) -> Arcs:
  """The cleaned, weighted, symmetric Graph500 graph of ``graph_seed``
  (default: ``seed``), its labels permuted by ``seed``."""
  graph_seed = seed if graph_seed is None else int(graph_seed)
  k_gen, k_w = jax.random.split(
      jax.random.wrap_key_data(seed_key(graph_seed)))
  row, col = kronecker_tuples(jax.random.key_data(k_gen), scale=scale,
                              edgefactor=edgefactor, abc=tuple(abc))
  n = 1 << scale
  # The label permutation is drawn on the host (a device permutation of
  # millions of labels takes the TPU compiler a minute) and applied on the
  # device.
  perm = np.random.default_rng([seed, 0]).permutation(n).astype(np.int32)
  lo, hi, w, count = _clean(jax.random.key_data(k_w), row, col,
                            jnp.asarray(perm), n=n)
  count = int(count)
  lo, hi, w = (np.asarray(x)[:count] for x in (lo, hi, w))
  return Arcs(n=n, src=np.concatenate([lo, hi]), dst=np.concatenate([hi, lo]),
              w=np.concatenate([w, w]), graph_seed=graph_seed, perm=perm)
