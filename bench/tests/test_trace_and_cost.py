"""The trace reduction and the kernel's byte count, checked by hand."""

import pathlib

import numpy as np
import pytest

from bench import kernel_cost
from bench import trace as tr

RECORDED = pathlib.Path(__file__).parent / "data" / "small.xplane.pb"


def ev(name, s, e):
  return tr.Event(name, float(s), float(e))


def test_merge_gaps_and_cover():
  merged = tr.merge([(5, 7), (0, 2), (1, 3), (7, 9), (12, 12), (11, 14)])
  assert merged == [(0, 3), (5, 9), (11, 14)]
  assert tr.covered(merged) == 3 + 4 + 3
  assert tr.gaps(merged, -1, 20) == [(-1, 0), (3, 5), (9, 11), (14, 20)]
  assert tr.gaps(tr.merge(tr.clip(merged, 2, 12)), 2, 12) == [(3, 5),
                                                              (9, 11)]


def test_summarize_by_hand():
  trace = tr.Trace(
      device={"/device:TPU:0": [ev("while.3", 10, 40), ev("fusion", 10, 20),
                                ev("ell_spmv", 15, 40),
                                ev("fusion", 60, 70), ev("copy", 95, 120)]},
      host=[ev(tr.WINDOW_SPAN, 0, 100), ev("bench.run", 5, 80),
            ev("sync", 42, 58)])
  s = tr.summarize(trace)
  assert s.window_s == pytest.approx(100e-9)
  # Busy: [10, 40) + [60, 70) + [95, 100) = 45 ns of the window.
  assert s.busy_s == pytest.approx(45e-9)
  assert s.op_seconds == pytest.approx(
      {"fusion": 20e-9, "ell_spmv": 25e-9, "copy": 5e-9})
  # Gaps: [0, 10) and [70, 95) go to the run; [40, 60) to the sync inside
  # it, which covers 16 ns of the gap to the run's 20.
  assert dict(s.idle_gaps) == pytest.approx(
      {"bench.run": 10e-9 + 25e-9, "sync": 20e-9})


def test_recorded_trace_union_and_sums():
  """The loader and the reduction agree with a direct count of the
  recorded v5e trace (two small PageRank runs through ell_spmv)."""
  if not RECORDED.exists():
    pytest.skip("no recorded trace")
  from jax.profiler import ProfileData
  data = ProfileData.from_file(str(RECORDED))
  ops, window = [], None
  for plane in data.planes:
    if plane.name.startswith("/device:"):
      for line in plane.lines:
        if line.name == "XLA Ops":
          ops += [(e.name.split(" = ")[0].lstrip("%"), e.start_ns, e.end_ns)
                  for e in line.events]
    elif plane.name.startswith("/host:"):
      for line in plane.lines:
        for e in line.events:
          if e.name == tr.WINDOW_SPAN:
            window = (e.start_ns, e.end_ns)
  assert ops and window
  lo, hi = window
  # Union by a sweep over the event boundaries inside the window.
  points = sorted([(max(s, lo), 1) for _, s, e in ops if e > lo and s < hi]
                  + [(min(e, hi), -1) for _, s, e in ops if e > lo and s < hi])
  depth, last, union = 0, None, 0.0
  for t, d in points:
    if depth > 0:
      union += t - last
    depth, last = depth + d, t
  sums = {}
  for name, s, e in ops:
    if name.startswith("while"):    # spans the operations of its loop
      continue
    s, e = max(s, lo), min(e, hi)
    if e > s:
      sums[name] = sums.get(name, 0.0) + (e - s)
  summary = tr.summarize(tr.read_xplane(str(RECORDED)))
  assert summary.window_s == pytest.approx((hi - lo) * 1e-9)
  assert summary.busy_s == pytest.approx(union * 1e-9)
  assert summary.op_seconds == pytest.approx(
      {k: v * 1e-9 for k, v in sums.items()})
  assert any("ell_spmv" in k for k in summary.op_seconds)
  assert 0 < summary.busy_s <= summary.window_s
  idle = sum(v for _, v in summary.idle_gaps)
  assert idle == pytest.approx(summary.window_s - summary.busy_s)


def test_ell_spmv_bytes_by_hand():
  """Five vertices; vertex 0 has in-arcs from 1, 2, 3 and vertex 1 from 0.
  Rows sort by in-degree and pad to 128-row blocks, so slots 0, 1 and 2
  each extend over one block of 128 rows and the other slots are empty."""
  from repro.core import graph as G
  ell = G.build_ell(np.array([1, 2, 3, 0]), np.array([0, 0, 0, 1]),
                    np.ones(4, np.float32), n=5)
  assert ell.slot_rows[:4] == (128, 128, 128, 0)
  # 3 slots x 128 rows x (4 B message + 4 B value + 1 B validity), plus
  # one 4 B result for each of the 128 rows of slot 0.
  assert kernel_cost.ell_spmv_bytes(ell.slot_rows, 1, 4, 4, 4) == (
      3 * 128 * 9 + 128 * 4)
  # Eight lanes: eight messages per slot entry and eight results per row.
  assert kernel_cost.ell_spmv_bytes(ell.slot_rows, 8, 4, 4, 4) == (
      3 * 128 * (8 * 4 + 4 + 1) + 128 * 8 * 4)
