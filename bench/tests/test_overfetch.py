"""The ``ell_gather_overfetch`` reader, by hand and on the program's own
layout at SCALE 10."""

import types

import jax
import pytest

from bench import harness
from repro.core.backends import Plan
from repro.kernels import ell_spmv


def ctx_of(slot_rows, n, plan):
  """A context as readers see it: the container already released."""
  return harness.Context(cell="g500-s21-ell.pagerank", seed=0,
                         config={"precision": "float32"}, traffic={},
                         arcs=types.SimpleNamespace(n=n), plan=plan,
                         layout={"slot_rows": slot_rows})


def read(ctx):
  return harness.load_part("metrics", "ell_gather_overfetch").read(ctx)


def overfetch(plan, rows):
  fetched = sum((s1 - s0) * r for s0, s1, r in plan)
  return 100 * (fetched / sum(rows) - 1)


def test_by_hand():
  """Slots 1 and 2 stay in slot 0's chunk (896 is 7/8 of 1024) and are
  fetched to 1024 rows; slot 3 starts a chunk of its own at 512."""
  ctx = ctx_of((1024, 896, 896, 512, 0), 1000, Plan(backend="pallas"))
  assert read(ctx) == pytest.approx(100 * ((3 * 1024 + 512) / 3328 - 1))
  # Equal extents: nothing is fetched past them.
  assert read(ctx_of((1024,) * 8, 1024, Plan(backend="pallas"))) == 0.0
  # block_slots caps the chunks: one slot each fetches only its extent.
  assert read(ctx_of((1024, 896, 896, 512), 1024,
                     Plan(backend="pallas", block_slots=1))) == 0.0


def test_nothing_to_read():
  assert read(ctx_of((1024, 512), 1024, Plan(backend="ell"))) is None
  assert read(ctx_of((), 1024, Plan(backend="pallas"))) is None


def test_program_without_chunk_plan(monkeypatch):
  """A program whose kernel has no ``chunk_plan`` gives no reading."""
  monkeypatch.delattr(ell_spmv, "chunk_plan")
  assert read(ctx_of((1024, 896), 1024, Plan(backend="pallas"))) is None


@pytest.mark.parametrize("cell", ["g500-s21-ell.pagerank",
                                  "g500-s21-ell.sssp"])
def test_on_the_cells_layout(monkeypatch, cell):
  """The cell's container and program at SCALE 10: the reading is that of
  the plan the kernel itself makes when the cell's program is traced."""
  orig = harness.load_json

  def load(path):
    d = orig(path)
    if path.parent.name == "configs":
      d["scale"] = 10
    return d
  monkeypatch.setattr(harness, "load_json", load)
  bench = harness.load_benchmark()
  entry = harness.cell_entry(bench, cell)
  traffic = harness.load_json(harness.BENCH_DIR / "traffic"
                              / f"{entry['traffic']}.json")
  ctx = harness.Context(cell=entry["name"], seed=2**31 + 5,
                        config=harness.load_json(
                            harness.BENCH_DIR / "configs"
                            / f"{entry['config']}.json"),
                        traffic=traffic, require_chip=False)
  harness.build_graph(ctx)

  plans = []
  orig_plan = ell_spmv.chunk_plan

  def recording(*a, **kw):
    plans.append(orig_plan(*a, **kw))
    return plans[-1]
  monkeypatch.setattr(ell_spmv, "chunk_plan", recording)
  batch = harness.load_part("programs", traffic["algorithm"]).Batch(ctx)
  jax.clear_caches()          # trace anew, so the kernel plans again
  jax.eval_shape(lambda g: batch.call(g, batch.keys[0]), ctx.graph)

  ctx.graph = None            # released before the readers run
  rows = ctx.layout["slot_rows"]
  assert plans and all(p == plans[0] for p in plans)
  assert read(ctx) == pytest.approx(overfetch(plans[0], rows))
  assert read(ctx) >= 0
