"""SSSP's plain reference, its control and its comparison.

The reference copies the ``Reference.sssp`` of the repository's
``chip_smoke.py``: scipy's Dijkstra in float64 on the host, along the
directed arcs the benchmark generated.  The control relaxes the same arcs
with distances and weights rounded to bfloat16 (sums in float32), one
precision below the configurations' float32, by Bellman-Ford sweeps in
numpy on the host (the same sweeps as a device scatter-min did not finish
24 sources at SCALE 18 in half an hour on a v5e).  Nothing here imports
the program.
"""

from __future__ import annotations

import numpy as np
import scipy.sparse as sp
from scipy.sparse import csgraph

NUMBERS = ("reach_mismatch", "dist_rel_err")


def reference(arcs, config, keys):
  """Distances from each root in ``keys`` (inf: unreached)."""
  fwd = sp.csr_matrix((np.asarray(arcs.w, np.float64), (arcs.src, arcs.dst)),
                      shape=(arcs.n, arcs.n))
  return list(np.atleast_2d(csgraph.dijkstra(fwd, directed=True,
                                             indices=list(keys))))


def _bf16(x):
  """float32 rounded to the nearest bfloat16, ties to even, as float32."""
  u = np.ascontiguousarray(x, np.float32).view(np.uint32)
  u = (u + np.uint32(0x7FFF) + ((u >> 16) & np.uint32(1))) & np.uint32(
      0xFFFF0000)
  return u.view(np.float32)


def control(arcs, config, keys):
  """Distances from each root in ``keys``, kept in bfloat16: every sweep
  relaxes every arc, f32(dist[src]) + bf16(w), takes the minimum per
  destination, rounds it to bfloat16 and keeps it where it is lower, until
  no distance changes."""
  order = np.argsort(arcs.dst, kind="stable")
  src, dst = arcs.src[order], arcs.dst[order]
  w = _bf16(arcs.w[order])
  starts = np.flatnonzero(np.r_[True, dst[1:] != dst[:-1]])
  heads = dst[starts]
  out = []
  for k in keys:
    dist = np.full(arcs.n, np.inf, np.float32)
    dist[k] = 0.0
    while True:
      cand = _bf16(np.minimum.reduceat(dist[src] + w, starts))
      new = np.minimum(dist[heads], cand)
      if np.array_equal(new, dist[heads]):
        break
      dist[heads] = new
    out.append(dist)
  return out


def numbers(pairs, n: int, missing: int = 0):
  """Over (answer, reference) pairs: ``reach_mismatch``, vertices reached on
  one side only, plus ``n`` for each answer ``missing`` (never given, or an
  error); ``dist_rel_err``, largest |got - ref| / ref over vertices both
  reach (a reference distance of 0 must be met exactly)."""
  tiny = np.finfo(np.float32).tiny
  mismatch, worst = missing * n, 0.0
  for got, ref in pairs:
    got = np.asarray(got, np.float64)
    g_fin, r_fin = np.isfinite(got), np.isfinite(ref)
    mismatch += int(np.sum(g_fin != r_fin)) + int(np.sum(np.isnan(got)))
    both = g_fin & r_fin
    if both.any():
      err = np.abs(got[both] - ref[both]) / np.maximum(ref[both], tiny)
      worst = max(worst, float(np.max(err)))
  return {"reach_mismatch": float(mismatch), "dist_rel_err": worst}
