"""``correct`` separates the program from its control and from planted
faults, through the harness's own run at SCALE 10 on the CPU.

The control is the reference computed with bfloat16 storage, one precision
below the configurations' float32: the comparison must judge it not correct
under the limits of every cell.  The faults break the timed path underneath
a whole run (device look skipped): each must turn ``correct`` false.
"""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, harness
from bench.gen import graph500

SCALE = 10
CAPACITY = 40_000
BATCH = ["g500-s21-ell.pagerank", "g500-s21-ell.sssp"]
SERVE = ["g500-s18-coo.serve-sssp"]


def traffic_of(cell):
  bench = harness.load_benchmark()
  entry = harness.cell_entry(bench, cell)
  return entry, harness.load_json(harness.BENCH_DIR / "traffic"
                                  / f"{entry['traffic']}.json")


@pytest.fixture
def small(monkeypatch):
  """Every configuration at SCALE 10 (the COO capacity cut to fit)."""
  orig = harness.load_json

  def load(path):
    d = orig(path)
    if path.parent.name == "configs":
      d["scale"] = SCALE
      if "capacity" in d["graph"]:
        d["graph"]["capacity"] = CAPACITY
    return d
  monkeypatch.setattr(harness, "load_json", load)


def run(cell, seed=2**31 + 99, **kw):
  return harness.run_cell(cell, seed, 1.0, False, require_chip=False, **kw)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_fails_every_cell(seed):
  a = graph500.generate(seed, scale=SCALE)
  roots = [int(v) for v in np.flatnonzero(np.bincount(a.src, minlength=a.n))[:4]]
  config = {"pagerank": {"sweeps": 10, "r": 0.15}}
  for cell in BATCH + SERVE:
    entry, traffic = traffic_of(cell)
    algo = traffic["algorithm"]
    keys = [None] if algo == "pagerank" else roots
    # The answers stand for the keys only: the control replaces them.
    answers = [(k, None) for k in keys]
    numbers = check.compare(algo, answers, a, config, answers_of="control")
    ok, table = check.judge(numbers, traffic["limits"])
    assert not ok, (cell, table)


@pytest.mark.parametrize("cell", BATCH + SERVE)
def test_sound_run_is_correct(small, cell):
  """A sound run is correct; the control at the same keys, as
  ``bench/calibrate.py`` reads it, is not."""
  r = run(cell, control=True)
  assert r["correct"], r["checks"]
  assert not check.judge({k: t["value"] for k, t in r["control"].items()},
                         {k: t["limit"] for k, t in r["checks"].items()})[0]
  assert r["attempted"] >= 1 and r["failed"] == 0
  assert list(r)[-1] == "checks"


def _frozen_superstep(graph, program, state, plan):
  """A superstep that returns its state unchanged (and ends the loop)."""
  return state._replace(active=jnp.zeros_like(state.active),
                        iteration=state.iteration + 1,
                        num_active=jnp.int32(0))


def _frozen_batched_superstep(graph, program, state, plan):
  return state._replace(active=jnp.zeros_like(state.active),
                        iteration=state.iteration + 1,
                        done=jnp.ones_like(state.done),
                        num_active=jnp.zeros_like(state.num_active),
                        iters=state.iters + 1)


@pytest.mark.parametrize("cell", BATCH + SERVE)
def test_state_unchanged_is_not_correct(small, monkeypatch, cell):
  from repro.core import engine
  monkeypatch.setattr(engine, "_superstep", _frozen_superstep)
  monkeypatch.setattr(engine, "_batched_superstep", _frozen_batched_superstep)
  jax.clear_caches()
  assert not run(cell)["correct"]


def _alter(x):
  """One vertex's answer off by one part in a thousand."""
  return x.at[x.shape[0] // 2].multiply(1.001)


@pytest.mark.parametrize("cell", BATCH)
def test_altered_batch_answer_is_not_correct(small, monkeypatch, cell):
  import importlib
  # The package exports functions of the same names as these modules.
  pagerank = importlib.import_module("repro.algos.pagerank")
  sssp = importlib.import_module("repro.algos.sssp")
  fixed, graph_program = pagerank.run_fixed_iters, sssp.run_graph_program

  def fixed_altered(*a, **k):
    s = fixed(*a, **k)
    return s._replace(prop={**s.prop, "rank": _alter(s.prop["rank"])})

  def graph_altered(*a, **k):
    s = graph_program(*a, **k)
    d = s.prop
    v = jnp.argmax(jnp.where(jnp.isfinite(d), d, -1.0))   # farthest reached
    return s._replace(prop=d.at[v].multiply(1.001))

  monkeypatch.setattr(pagerank, "run_fixed_iters", fixed_altered)
  monkeypatch.setattr(sssp, "run_graph_program", graph_altered)
  jax.clear_caches()
  assert not run(cell)["correct"]


@pytest.mark.parametrize("cell", SERVE)
def test_altered_served_answer_is_not_correct(small, monkeypatch, cell):
  from repro.service import scheduler
  orig = scheduler.SsspFamily.extract

  def extract(self, prop_col):
    d = np.array(orig(self, prop_col))
    v = int(np.argmax(np.where(np.isfinite(d), d, -1.0)))
    d[v] *= 1.001
    return d
  monkeypatch.setattr(scheduler.SsspFamily, "extract", extract)
  assert not run(cell)["correct"]


@pytest.mark.parametrize("cell", SERVE)
def test_half_the_slots_left_out_is_not_correct(small, monkeypatch, cell):
  """The batched superstep computes only the first half of its slots; the
  other half keep their state and are reported done."""
  from repro.core import engine
  orig = engine._batched_superstep

  def half(graph, program, state, plan):
    new = orig(graph, program, state, plan)
    q = state.done.shape[0]
    keep = jnp.arange(q) < q // 2
    prop = jax.tree_util.tree_map(
        lambda a, b: jnp.where(keep[None, :], a, b), new.prop, state.prop)
    return new._replace(prop=prop, active=new.active & keep[None, :],
                        done=new.done | ~keep,
                        num_active=jnp.where(keep, new.num_active, 0))
  monkeypatch.setattr(engine, "_batched_superstep", half)
  jax.clear_caches()
  assert not run(cell)["correct"]
