"""CDLP's plain reference, its control and its comparison.

LDBC Graphalytics CDLP (arXiv:2011.15028, its CDLP section) in numpy on the
host, along the arcs the benchmark generated: labels start as the vertex
ids; in each of the configuration's iterations, all at once, a vertex takes
the label that occurs most often among its in-neighbours' labels (on these
symmetric graphs, its neighbours'), ties to the smallest; its own label does
not count, and a vertex with no neighbour keeps its label.  Each iteration
is one int64 sort of the packed keys ``dst * n + label[src]``, so that a
destination's equal labels form runs.

The control breaks ties toward the largest label, a plausible fault.  There
is no control one precision below: the labels are integers, and an exact
integer answer has no lower precision to compute in.  Nothing here imports
the program.
"""

from __future__ import annotations

import numpy as np

NUMBERS = ("label_mismatch",)


def _cdlp(arcs, iterations: int, smallest: bool) -> np.ndarray:
  n = arcs.n
  src = np.asarray(arcs.src, np.int64)
  dst = np.asarray(arcs.dst, np.int64) * n
  labels = np.arange(n, dtype=np.int64)
  for _ in range(iterations):
    key = np.sort(dst + labels[src])
    starts = np.flatnonzero(np.r_[True, key[1:] != key[:-1]])
    counts = np.diff(np.r_[starts, key.size])
    run_dst, run_lab = np.divmod(key[starts], n)
    heads = np.flatnonzero(np.r_[True, run_dst[1:] != run_dst[:-1]])
    best = np.repeat(np.maximum.reduceat(counts, heads),
                     np.diff(np.r_[heads, counts.size]))
    # A destination's runs come in increasing label order.
    if smallest:
      won = np.minimum.reduceat(np.where(counts == best, run_lab, n), heads)
    else:
      won = np.maximum.reduceat(np.where(counts == best, run_lab, -1), heads)
    labels[run_dst[heads]] = won
  return labels


def _iterations(config) -> int:
  return int(config["cdlp"]["iterations"])


def reference(arcs, config, keys):
  """The labels after the configuration's iterations, one answer per key
  (all alike: every run is the same)."""
  labels = _cdlp(arcs, _iterations(config), smallest=True)
  return [labels for _ in keys]


def control(arcs, config, keys):
  labels = _cdlp(arcs, _iterations(config), smallest=False)
  return [labels for _ in keys]


def numbers(pairs, n: int, missing: int = 0):
  """``label_mismatch``: vertices whose final label differs, over every
  answer, plus ``n`` for each answer ``missing``."""
  mismatch = missing * n
  for got, ref in pairs:
    got = np.asarray(got)
    mismatch += n if got.shape != ref.shape else int(np.sum(got != ref))
  return {"label_mismatch": float(mismatch)}
