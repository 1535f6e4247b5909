"""The readings of a profile by the program's own names (``bench.scopes``),
checked by hand and on two recorded v5e profiles."""

import pathlib

import pytest

from bench import scopes as sc
from bench import trace as tr

DATA = pathlib.Path(__file__).parent / "data"
SMALL = DATA / "small.xplane.pb"        # recorded before the scopes existed
SCOPED = DATA / "scoped.xplane.pb"      # record_scoped.py


def ev(name, s, e):
  return tr.Event(name, float(s), float(e))


def test_phase_of_takes_the_outermost_scope():
  assert sc.phase_of("jit(f)/while/body/graphmat/spmv/gather/gather") == (
      "spmv/gather")
  assert sc.phase_of("jit(f)/while/body/graphmat/spmv/spill/graphmat/spmv/"
                     "scatter/scatter-min") == "spmv/spill"
  assert sc.phase_of("jit(f)/closed_call/cond/branch_0_fun/graphmat/spmv/"
                     "kernel/ell_spmv/pallas_call") == "spmv/kernel"
  assert sc.phase_of("jit(f)/graphmat/apply/min") == "apply"
  assert sc.phase_of("jit(_install)/graphmat/install/scatter") == "install"
  assert sc.phase_of("jit(f)/while/body/add") == sc.UNSCOPED
  assert sc.phase_of("") == sc.UNSCOPED


def test_split_busy_shares_overlaps_and_fills_with_control():
  got = sc.split_busy([(0, 10, "a"), (5, 15, "b"), (20, 30, "a"),
                       (-5, 40, "ctl")], 0, 35, under="ctl")
  # [0, 5) a; [5, 10) half each; [10, 15) b; [15, 20) and [30, 35) only
  # the control flow; [20, 30) a.
  assert got == pytest.approx({"a": 5 + 2.5 + 10, "b": 2.5 + 5,
                               "ctl": 5 + 5})
  assert sum(got.values()) == pytest.approx(35)


def test_phase_seconds_add_up_to_busy():
  ops = [sc.Op("while.1", "m", sc.UNSCOPED, 0, 100),
         sc.Op("fusion.1", "m", "spmv/gather", 10, 40),
         sc.Op("ell_spmv.2", "m", "spmv/kernel", 40, 60),
         sc.Op("fusion.3", "m", "apply", 70, 90),
         sc.Op("copy.4", "m", sc.UNSCOPED, 120, 130)]
  secs, count = sc.phase_seconds({"/device:TPU:0": ops}, 0, 125)
  assert secs == pytest.approx({"spmv/gather": 30e-9, "spmv/kernel": 20e-9,
                                "apply": 20e-9,
                                sc.UNSCOPED: (10 + 10 + 10 + 5) * 1e-9})
  assert count == {"spmv/gather": 1, "spmv/kernel": 1, "apply": 1,
                   sc.UNSCOPED: 1}
  trace = tr.Trace({"/device:TPU:0": [tr.Event(o.name, o.start_ns, o.end_ns)
                                      for o in ops]},
                   [ev(tr.WINDOW_SPAN, 0, 125)])
  assert sum(secs.values()) == pytest.approx(tr.summarize(trace).busy_s)


def test_spans_and_idle_by_innermost_span():
  host = [ev("graphmat.round", 0, 100), ev("graphmat.round.admit", 0, 20),
          ev("graphmat.install", 5, 15), ev("graphmat.round.supersteps",
                                            20, 70),
          ev("graphmat.sync", 60, 70), ev("graphmat.round.retire", 70, 100),
          ev("graphmat.sync", 72, 75), ev("graphmat.extract", 80, 95),
          ev("graphmat.sync", 85, 95), ev("bench.result", 0, 200),
          ev("graphmat.round", 150, 160)]
  secs, count = sc.span_seconds(host, 0, 155)
  assert secs["graphmat.round"] == pytest.approx(105e-9)
  assert secs["graphmat.sync"] == pytest.approx(23e-9)
  assert count == {"graphmat.round": 2, "graphmat.round.admit": 1,
                   "graphmat.install": 1, "graphmat.round.supersteps": 1,
                   "graphmat.sync": 3, "graphmat.round.retire": 1,
                   "graphmat.extract": 1}
  spans = [e for e in host if e.name.startswith(sc.SPAN_PREFIX)]
  idle = [(2, 12), (65, 78), (90, 110), (120, 152)]
  got = sc.idle_under(idle, spans)
  # Innermost pieces: admit [0, 5) and [15, 20), install [5, 15),
  # supersteps [20, 60), sync [60, 70), [72, 75), [85, 95), retire
  # [70, 72), [75, 80), [95, 100), extract [80, 85), round [150, 160).
  assert got == pytest.approx({
      "graphmat.round.admit": 3e-9, "graphmat.install": 7e-9,
      "graphmat.sync": (5 + 3 + 5) * 1e-9,
      "graphmat.round.retire": (2 + 3 + 5) * 1e-9,
      "graphmat.round": 2e-9, "outside": (10 + 30) * 1e-9})
  assert sum(got.values()) == pytest.approx(sum(e - s for s, e in idle)
                                            * 1e-9)
  rounds = sc.idle_under(idle, [e for e in spans
                                if e.name in sc.ROUND_SPANS])
  assert rounds == pytest.approx({
      "graphmat.round.admit": 10e-9, "graphmat.round.supersteps": 5e-9,
      "graphmat.round.retire": 8e-9 + 10e-9, "graphmat.round": 2e-9,
      "outside": 40e-9})


def test_hlo_op_names_from_a_cpu_profile(tmp_path):
  """The wire-format reader finds each instruction's op_name in the HLO a
  CPU profile records (the CPU runs no device plane, so no device ops)."""
  import jax
  import jax.numpy as jnp

  @jax.jit
  def f(x, i):
    with jax.named_scope("graphmat/spmv/gather"):
      y = x[i]
    with jax.named_scope("graphmat/apply"):
      return jnp.sin(y) * 2

  x, i = jnp.arange(64.0), jnp.arange(64)[::-1]
  f(x, i).block_until_ready()
  with jax.profiler.trace(str(tmp_path),
                          profiler_options=sc.profiler_options()):
    f(x, i).block_until_ready()
  hlo = sc.read_hlo(tr.find_xplane(str(tmp_path)))
  (module,) = [m for m in hlo if m.startswith("jit_f(")]
  phases = {sc.phase_of(n) for n in hlo[module].values()}
  assert {"spmv/gather", "apply"} <= phases


def test_small_trace_reads_as_before():
  """The trace the benchmark has read since before the scopes: its summary
  is unchanged, and every operation in it is unscoped."""
  if not SMALL.exists():
    pytest.skip("no recorded trace")
  s = tr.summarize(tr.read_xplane(str(SMALL)))
  assert s.window_s == pytest.approx(0.0324169, rel=1e-9)
  assert s.busy_s == pytest.approx(0.029124609, rel=1e-9)
  assert len(s.op_seconds) == 61
  top = tr.top(s.op_seconds, 3)
  assert [name for name, _ in top] == ["fusion.56", "fusion.55", "fusion.54"]
  assert [sec for _, sec in top] == pytest.approx(
      [0.014245992, 0.011854044, 0.000824168])
  assert [name for name, _ in s.idle_gaps] == ["bench.run"]
  assert s.idle_gaps[0][1] == pytest.approx(0.003292291)
  r = sc.read(str(SMALL))
  assert r.window_s == s.window_s and r.busy_s == s.busy_s
  assert r.phase_s == pytest.approx({sc.UNSCOPED: s.busy_s})
  assert r.span_n == {} and r.idle_s == pytest.approx(
      {sc.OUTSIDE: s.window_s - s.busy_s})


@pytest.fixture(scope="module")
def scoped():
  if not SCOPED.exists():
    pytest.skip("no recorded scoped trace")
  return sc.read(str(SCOPED))


def test_scoped_trace_shares_add_up(scoped):
  """A v5e profile of one PageRank run on the Pallas ELL path and three
  served SSSP rounds on COO: every phase shows, and the phases' shares and
  the unscoped share make the whole busy time."""
  r = scoped
  shares = {k: 100.0 * v / r.busy_s for k, v in r.phase_s.items()}
  assert sum(shares.values()) == pytest.approx(100.0)
  assert {"send", "spmv/gather", "spmv/kernel", "spmv/unpermute",
          "spmv/spill", "spmv/scatter", "apply", "install",
          "extract"} <= set(shares)
  assert shares[sc.UNSCOPED] < 5.0
  kernels = {o.phase for ops in sc.read_ops(str(SCOPED)).values()
             for o in ops if o.name.startswith("ell_spmv")}
  assert kernels == {"spmv/kernel"}


def test_scoped_trace_rounds_and_syncs(scoped):
  r = scoped
  n = r.span_n
  assert n["graphmat.round"] == 3
  for child in ("graphmat.round.admit", "graphmat.round.supersteps",
                "graphmat.round.retire"):
    assert n[child] == 3
  assert n["graphmat.install"] >= 1 and n["graphmat.extract"] >= 1
  assert n["graphmat.sync"] == 3 * 3 + n["graphmat.extract"]
  assert sum(r.idle_s.values()) == pytest.approx(r.window_s - r.busy_s)
  assert sum(r.idle_round_s.values()) == pytest.approx(
      r.window_s - r.busy_s)
  got = sc.numbers(r)
  assert got["syncs_per_round"] == pytest.approx(
      3 + n["graphmat.extract"] / 3)
  assert got["host_round_ms"] == pytest.approx(1e3 * (
      r.idle_round_s["graphmat.round.admit"]
      + r.idle_round_s["graphmat.round.retire"]) / 3)
  assert 0 < got["round_idle_share"] < 100
  assert sum(got["phase_share"].values()) == pytest.approx(100.0)


def test_numbers_by_hand():
  r = sc.Reading(
      window_s=10.0, busy_s=8.0, phase_s={"spmv/gather": 6.0,
                                          sc.UNSCOPED: 2.0},
      phase_n={}, span_s={}, span_n={"graphmat.round": 4,
                                     "graphmat.sync": 14},
      idle_s={}, idle_round_s={"graphmat.round.admit": 1.2,
                               "graphmat.round.retire": 0.4,
                               "graphmat.round.supersteps": 0.2,
                               sc.OUTSIDE: 0.2}, top_ops=[])
  got = sc.numbers(r)
  assert got.pop("phase_share") == pytest.approx(
      {"spmv/gather": 75.0, sc.UNSCOPED: 25.0})
  assert got == pytest.approx({"host_round_ms": 400.0,
                               "syncs_per_round": 3.5,
                               "round_idle_share": 90.0})
  r.span_n = {}
  assert set(sc.numbers(r)) == {"phase_share"}
