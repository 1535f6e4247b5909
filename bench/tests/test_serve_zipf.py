"""The served Zipf cell: its source stream, and on the CPU at SCALE 10 a
whole run through the harness that is ``correct``, and one with an altered
answer that is not."""

import time

import numpy as np
import pytest

from bench import harness

CELL = "g500-s18-coo.serve-zipf"
SCALE = 10
CAPACITY = 40_000
SPEC = {"dist": "zipf", "theta": 0.99, "reorder_s": 10}


def sources(candidates, seed=5):
  return harness.load_part("drivers", "serve_zipf").Sources(
      SPEC, candidates, seed)


def test_top_source_takes_its_zipf_share():
  cand = np.arange(3, 20_003) * 7
  s = sources(cand)
  draws = np.array([s.next() for _ in range(200_000)])
  assert np.isin(draws, cand).all()
  ranks = np.arange(1, cand.size + 1, dtype=np.float64)
  want = 1.0 / np.sum(ranks ** -SPEC["theta"])        # rank 1's share
  top = np.mean(draws == s.order[0])
  assert top == pytest.approx(want, rel=0.05)
  second = np.mean(draws == s.order[1])
  assert second == pytest.approx(want * 2 ** -SPEC["theta"], rel=0.07)


def test_order_moves_every_period():
  s = sources(np.arange(1000))
  first = s.order.copy()
  s.start(time.perf_counter())                 # window opens: epoch 0
  s.next()
  np.testing.assert_array_equal(s.order, first)
  s.start(time.perf_counter() - 10.5)          # 10.5 s into the window
  s.next()
  assert s.epoch == 1
  assert not np.array_equal(s.order, first)
  np.testing.assert_array_equal(np.sort(s.order), np.arange(1000))
  np.testing.assert_array_equal(sources(np.arange(1000)).order_of(1),
                                s.order)       # drawn from the seed


@pytest.fixture
def small(monkeypatch):
  """The configuration at SCALE 10, its capacity cut to fit."""
  orig = harness.load_json

  def load(path):
    d = orig(path)
    if path.parent.name == "configs":
      d["scale"] = SCALE
      d["graph"]["capacity"] = CAPACITY
    return d
  monkeypatch.setattr(harness, "load_json", load)


def run():
  return harness.run_cell(CELL, 2**31 + 55, 2.0, False, require_chip=False)


def test_sound_run_is_correct(small):
  r = run()
  assert r["correct"], r["checks"]
  assert r["attempted"] >= 1 and r["failed"] == 0
  assert set(r["metrics"]) == {"qps", "p95_ms", "setup_s"}


def test_altered_served_answer_is_not_correct(small, monkeypatch):
  from repro.service import scheduler
  orig = scheduler.SsspFamily.extract

  def extract(self, prop_col):
    d = np.array(orig(self, prop_col))
    v = int(np.argmax(np.where(np.isfinite(d), d, -1.0)))
    d[v] *= 1.001
    return d
  monkeypatch.setattr(scheduler.SsspFamily, "extract", extract)
  assert not run()["correct"]
