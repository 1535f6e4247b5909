"""The ``ell_split_share`` reader, on the program's own layout at SCALE 10."""

import numpy as np
import pytest

from bench import harness
from repro.core import graph as G


def read(ctx):
  return harness.load_part("metrics", "ell_split_share").read(ctx)


def cell_ctx(monkeypatch, cell):
  """The cell's context at SCALE 10, its container built."""
  orig = harness.load_json

  def load(path):
    d = orig(path)
    if path.parent.name == "configs":
      d["scale"] = 10
    return d
  monkeypatch.setattr(harness, "load_json", load)
  entry = harness.cell_entry(harness.load_benchmark(), cell)
  ctx = harness.Context(cell=entry["name"], seed=2**31 + 7,
                        config=harness.load_json(
                            harness.BENCH_DIR / "configs"
                            / f"{entry['config']}.json"),
                        traffic={}, require_chip=False)
  harness.build_graph(ctx)
  return ctx


@pytest.mark.parametrize("cell", ["g500-s21-ell.pagerank",
                                  "g500-s21-ell.sssp"])
def test_reads_the_split_rows_build_ell_makes(monkeypatch, cell):
  """The reading is the share of the arcs that the cell's container put in
  its split rows."""
  ctx = cell_ctx(monkeypatch, cell)
  g = ctx.graph
  mask = np.asarray(g.mask)
  in_split = int(mask[:, np.asarray(g.split_rows)].sum())
  assert in_split > 0
  want = 100.0 * in_split / int(mask.sum())
  ctx.graph = None            # released before the readers run
  assert read(ctx) == pytest.approx(want)
  assert 0 < read(ctx) < 100


def test_nothing_to_read(monkeypatch):
  """A COO cell, or a program without ``ell_split``, gives no reading."""
  ctx = cell_ctx(monkeypatch, "g500-s21-coo.cdlp")
  assert read(ctx) is None
  ctx = cell_ctx(monkeypatch, "g500-s21-ell.pagerank")
  monkeypatch.delattr(G, "ell_split")
  assert read(ctx) is None
