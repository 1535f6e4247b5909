"""The CDLP cell on the CPU at SCALE 10: its control fails ``correct``, a
whole run through the harness is ``correct``, and a run whose answer has one
label changed is not."""

import jax
import pytest

from bench import check, harness
from bench.gen import graph500

CELL = "g500-s21-coo.cdlp"
SCALE = 10
CAPACITY = 40_000


def traffic():
  entry = harness.cell_entry(harness.load_benchmark(), CELL)
  return harness.load_json(harness.BENCH_DIR / "traffic"
                           / f"{entry['traffic']}.json")


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


def run(**kw):
  return harness.run_cell(CELL, 2**31 + 77, 1.0, False, require_chip=False,
                          **kw)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_fails(seed):
  a = graph500.generate(seed, scale=SCALE, graph_seed=1)
  config = {"cdlp": {"iterations": 10}}
  numbers = check.compare("cdlp", [(None, None)], a, config,
                          answers_of="control")
  ok, table = check.judge(numbers, traffic()["limits"])
  assert not ok, table


def test_sound_run_is_correct(small):
  r = run(control=True)
  assert r["correct"], r["checks"]
  assert r["checks"]["label_mismatch"]["value"] == 0
  assert r["control"]["label_mismatch"]["value"] > 0
  assert r["attempted"] >= 1 and r["failed"] == 0
  assert r["metrics"]["teps"]["value"] > 0


def test_one_label_changed_is_not_correct(small, monkeypatch):
  import importlib
  cdlp = importlib.import_module("repro.algos.cdlp")
  fixed = cdlp.run_fixed_iters

  def altered(*a, **k):
    s = fixed(*a, **k)
    return s._replace(prop=s.prop.at[s.prop.shape[0] // 2].add(1))

  monkeypatch.setattr(cdlp, "run_fixed_iters", altered)
  jax.clear_caches()
  r = run()
  assert not r["correct"]
  # One vertex off in every run of the window.
  assert r["checks"]["label_mismatch"]["value"] == r["attempted"]


def test_mode_sort_share_reads_the_mode_scope():
  """The reader sums the operations the compiled CDLP program puts under
  ``graphmat/spmv/mode``, and nothing else."""
  from bench.trace import Summary
  from repro.core.backends import Plan
  metric = harness.load_part("metrics", "mode_sort_share.cdlp")
  ctx = harness.Context(cell=CELL, seed=0, config={
      "graph": {"capacity": 30_000}, "cdlp": {"iterations": 2}},
      traffic={}, plan=Plan("coo"),
      arcs=graph500.generate(4, scale=9, graph_seed=1))
  assert metric.read(ctx) is None
  names = metric.mode_ops(ctx)
  assert any(name.startswith("sort") for name in names)
  ops = {name: 0.5 for name in names}
  ctx.summary = Summary(window_s=9.0, busy_s=4.0 * len(ops), idle_gaps=[],
                        op_seconds={**ops, "not-mode.1": 2.0 * len(ops)})
  assert metric.read(ctx) == pytest.approx(12.5)
  ctx.summary.op_seconds = {"not-mode.1": 1.0}
  assert metric.read(ctx) is None
