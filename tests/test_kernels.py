"""Per-kernel allclose sweeps: Pallas (interpret) vs ref.py oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ell_spmv
from repro.kernels.ell_spmv import chunk_plan, ell_spmv_pallas
from repro.kernels.ref import ell_spmv_ref


def make_ell(rng, n_pad, width, n_src, dtype):
  """Slot-major ELL arrays ``[width, n_pad]``."""
  cols = rng.integers(0, n_src, (width, n_pad)).astype(np.int32)
  vals = rng.uniform(0.1, 2.0, (width, n_pad)).astype(dtype)
  mask = rng.uniform(size=(width, n_pad)) > 0.3
  return jnp.asarray(cols), jnp.asarray(vals), jnp.asarray(mask)


PROCS = {
    "min_plus": (lambda m, e, d: m + e, "min"),
    "plus_times": (lambda m, e, d: m * e, "add"),
    "max_times": (lambda m, e, d: m * e, "max"),
    "plus_dst": (lambda m, e, d: (e - m * d) * m, "add"),
}


@pytest.mark.parametrize("shape", [(8, 8, 8, 1), (64, 16, 100, 1),
                                   (128, 24, 50, 4), (256, 8, 256, 8)])
@pytest.mark.parametrize("sem", sorted(PROCS))
def test_kernel_matches_ref(shape, sem):
  n_pad, width, n_src, k = shape
  rng = np.random.default_rng(hash((shape, sem)) % 2**32)
  cols, vals, mask = make_ell(rng, n_pad, width, n_src, np.float32)
  msg = jnp.asarray(rng.standard_normal((n_src, k)).astype(np.float32))
  act = jnp.asarray(rng.uniform(size=n_src) > 0.2)
  dprop = jnp.asarray(rng.standard_normal((n_pad, k)).astype(np.float32))
  proc, kind = PROCS[sem]
  yk, rk = ell_spmv_pallas(cols, vals, mask, msg, act, dprop,
                           process=proc, reduce_kind=kind)
  yr, rr = ell_spmv_ref(cols, vals, mask, msg, act, dprop,
                        process=proc, reduce_kind=kind)
  np.testing.assert_array_equal(np.asarray(rk), np.asarray(rr))
  np.testing.assert_allclose(np.asarray(yk), np.asarray(yr),
                             rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_kernel_dtypes(dtype):
  rng = np.random.default_rng(0)
  cols, vals, mask = make_ell(rng, 32, 8, 40, dtype)
  msg = jnp.asarray(rng.uniform(0, 2, (40,)).astype(dtype))
  act = jnp.ones((40,), bool)
  proc = lambda m, e, d: m + e
  yk, _ = ell_spmv_pallas(cols, vals, mask, msg, act,
                          process=proc, reduce_kind="min")
  yr, _ = ell_spmv_ref(cols, vals, mask, msg, act,
                       process=proc, reduce_kind="min")
  np.testing.assert_allclose(np.asarray(yk, np.float32),
                             np.asarray(yr, np.float32), rtol=1e-2)


@pytest.mark.parametrize("br,bw", [(8, 8), (16, 24), (None, None)])
def test_kernel_block_shapes(br, bw):
  """Tiling must not change results (accumulation across slot tiles)."""
  rng = np.random.default_rng(4)
  cols, vals, mask = make_ell(rng, 48, 48, 64, np.float32)
  msg = jnp.asarray(rng.standard_normal((64,)).astype(np.float32))
  act = jnp.asarray(rng.uniform(size=64) > 0.4)
  proc = lambda m, e, d: m * e
  y0, _ = ell_spmv_pallas(cols, vals, mask, msg, act,
                          process=proc, reduce_kind="add")
  yk, _ = ell_spmv_pallas(cols, vals, mask, msg, act,
                          process=proc, reduce_kind="add",
                          block_rows=br, block_slots=bw)
  np.testing.assert_allclose(np.asarray(yk), np.asarray(y0), rtol=1e-5)


def test_kernel_all_inactive():
  rng = np.random.default_rng(5)
  cols, vals, mask = make_ell(rng, 16, 8, 16, np.float32)
  msg = jnp.ones((16,), jnp.float32)
  act = jnp.zeros((16,), bool)
  yk, rk = ell_spmv_pallas(cols, vals, mask, msg, act,
                           process=lambda m, e, d: m + e,
                           reduce_kind="min")
  assert not np.any(np.asarray(rk))
  assert np.all(np.isinf(np.asarray(yk)))


@pytest.mark.parametrize("bq", [1, 2, 8])
def test_kernel_lane_blocks(bq):
  """Lane blocking (one gather + kernel call per block) is invisible."""
  rng = np.random.default_rng(6)
  cols, vals, mask = make_ell(rng, 64, 16, 80, np.float32)
  msg = jnp.asarray(rng.integers(0, 50, (80, 8)).astype(np.int32))
  act = jnp.asarray(rng.uniform(size=80) > 0.3)
  proc = lambda m, e, d: m + 1
  yk, rk = ell_spmv_pallas(cols, vals, mask, msg, act, process=proc,
                           reduce_kind="min", block_queries=bq)
  yr, rr = ell_spmv_ref(cols, vals, mask, msg, act, process=proc,
                        reduce_kind="min")
  np.testing.assert_array_equal(np.asarray(rk), np.asarray(rr))
  np.testing.assert_array_equal(np.asarray(yk), np.asarray(yr))


@pytest.mark.parametrize("lanes", [None, 8], ids=["scalar", "q8"])
def test_kernel_row_extents(rmat_small, monkeypatch, lanes):
  """build_ell's slot_rows bound every slot's edges, and the kernel, which
  then gathers each slot chunk to its first slot's extent (in many small
  chunks here), matches the full-width reference."""
  from repro.core.graph import build_ell
  n, src, dst, w = rmat_small
  g = build_ell(src, dst, w, n=n)
  mask = np.asarray(g.mask)
  assert list(g.slot_rows) == sorted(g.slot_rows, reverse=True)
  assert min(g.slot_rows) < g.n_pad           # some padding is skipped
  for s, r in enumerate(g.slot_rows):
    assert not mask[s, r:].any()
  monkeypatch.setattr(ell_spmv, "GATHER_BYTES", 4096)
  rng = np.random.default_rng(7)
  shape = (n,) if lanes is None else (n, lanes)
  msg = jnp.asarray(rng.uniform(0, 5, shape).astype(np.float32))
  act = jnp.asarray(rng.uniform(size=n) > 0.3)
  proc = lambda m, e, d: m + e
  yk, rk = ell_spmv_pallas(g.cols, g.vals, g.mask, msg, act, process=proc,
                           reduce_kind="min", slot_rows=g.slot_rows)
  yr, rr = ell_spmv_ref(g.cols, g.vals, g.mask, msg, act, process=proc,
                        reduce_kind="min")
  np.testing.assert_array_equal(np.asarray(rk), np.asarray(rr))
  np.testing.assert_array_equal(np.asarray(yk), np.asarray(yr))


# ---------------------------------------------------------------------------
# Slot chunks of the ELL kernel
# ---------------------------------------------------------------------------

N_PL = 1 << 22


def _power_law(width=128):
  """Row extents of a Graph500-like ELL (as tests/test_tpu_compile.py
  describes scale 22): 0.52 N (1 + s)^-0.7, rounded up to 128 rows."""
  return tuple(min(N_PL, -(-int(0.52 * N_PL * (1 + s) ** -0.7) // 128) * 128)
               for s in range(width))


def _greedy_plan(rows, n_pad, bq, isz, unit, block_slots=None):
  """The schedule before extent cuts: each chunk as wide as the budgets
  allow at its first slot's extent."""
  budget = ell_spmv.GATHER_BYTES // isz
  chunks, s0 = [], 0
  while s0 < len(rows) and rows[s0] > 0:
    r = min(n_pad, -(-rows[s0] // unit) * unit)
    cw = block_slots or max(1, min(budget // (bq * r),
                                   ell_spmv.TILE_BYTES // (isz * bq * unit)))
    cw = cw - cw % 8 if cw > 8 else cw
    chunks.append((s0, min(len(rows), s0 + cw), r))
    s0 += cw
  return chunks


def _overfetch(plan, rows):
  return sum((s1 - s0) * r for s0, s1, r in plan) / sum(rows) - 1


PROFILES = {
    "power_law": (_power_law(), N_PL),
    "trailing_empty": (_power_law(40) + (0,) * 24, N_PL),
    "steps": ((4096,) * 3 + (2048,) * 9 + (1024,) * 5 + (128,) * 30 + (0,) * 2,
              4096),
    "random": (tuple(sorted(np.random.default_rng(3).integers(0, 64, 50) * 128,
                            reverse=True)), 8192),
}
PLAN_ARGS = [(1, 4, 128, None), (8, 4, 128, None), (2, 2, 128, None),
             (1, 4, 128, 16), (1, 4, 256, 5)]


@pytest.mark.parametrize("args", PLAN_ARGS, ids=str)
@pytest.mark.parametrize("profile", sorted(PROFILES))
def test_chunk_plan_covers_bounds_and_budgets(profile, args):
  """Each slot of nonzero extent lies in one chunk, in order; a chunk's r
  covers its slots' extents in whole row units; a chunk keeps to the byte
  budget or is one slot wide, and to ``block_slots``."""
  rows, n_pad = PROFILES[profile]
  bq, isz, unit, block_slots = args
  plan = chunk_plan(rows, n_pad, bq, isz, unit, block_slots)
  nonzero = [s for s, x in enumerate(rows) if x > 0]
  assert [s for s0, s1, _ in plan for s in range(s0, s1)] == nonzero
  for s0, s1, r in plan:
    assert s1 > s0
    assert r % unit == 0 or r == n_pad
    assert all(rows[s] <= r for s in range(s0, s1))
    assert (bq * (s1 - s0) * r * isz <= ell_spmv.GATHER_BYTES
            or s1 - s0 == 1 or block_slots)
    assert s1 - s0 <= (block_slots or len(rows))


@pytest.mark.parametrize("args", PLAN_ARGS, ids=str)
def test_chunk_plan_equal_extents_as_before(args):
  """On a regular graph (every slot at one extent) nothing is cut: the plan
  is the greedy one the budgets give."""
  bq, isz, unit, block_slots = args
  for rows, n_pad in [((1 << 20,) * 96, 1 << 20), ((512,) * 40, 512),
                      ((3 << 18,) * 200, 1 << 20)]:
    assert (chunk_plan(rows, n_pad, bq, isz, unit, block_slots)
            == _greedy_plan(rows, n_pad, bq, isz, unit, block_slots))


def test_chunk_plan_budget_is_the_only_cap_on_equal_extents(monkeypatch):
  """A small gather budget splits equal extents into budget-wide chunks."""
  monkeypatch.setattr(ell_spmv, "GATHER_BYTES", 64 * 1024)
  plan = chunk_plan((1024,) * 40, 1024, 1, 4, 128)
  assert plan == [(0, 16, 1024), (16, 32, 1024), (32, 40, 1024)]


@pytest.mark.parametrize("lanes,greedy", [(1, 1.0), (8, 0.3)])
def test_chunk_plan_power_law_overfetch(lanes, greedy):
  """On a power-law profile the extent cuts fetch under 25% beyond the
  extents, where the greedy schedule fetches over 100% (over 30% at eight
  lanes, whose budget already narrows its chunks)."""
  rows = _power_law()
  plan = chunk_plan(rows, N_PL, lanes, 4, 128)
  assert _overfetch(plan, rows) < 0.25
  assert _overfetch(_greedy_plan(rows, N_PL, lanes, 4, 128), rows) > greedy
  assert all(rows[s1] < ell_spmv.EXTENT_CUT * rows[s0]
             for s0, s1, _ in plan[:-1])


@pytest.fixture(scope="module")
def power_law_ell():
  """A scale-11 R-MAT graph in build_ell's layout: its plan at the default
  budgets has at least three chunks."""
  from repro.core.graph import build_ell
  from repro.graphs import dedupe_edges, remove_self_loops, rmat_edges
  src, dst = rmat_edges(11, 8, seed=5)
  src, dst = dedupe_edges(*remove_self_loops(src, dst))
  n = 1 << 11
  w = np.random.default_rng(1).uniform(0.1, 2.0, len(src)).astype(np.float32)
  g = build_ell(src, dst, w, n=n)
  assert len(chunk_plan(g.slot_rows, g.n_pad, 1, 4, 128)) >= 3
  return n, g


@pytest.mark.parametrize("frontier", [0.9, 0.05], ids=["dense", "sparse"])
@pytest.mark.parametrize("sem", ["min_plus", "plus_times"])
@pytest.mark.parametrize("lanes", [None, 8], ids=["scalar", "q8"])
def test_kernel_extent_chunks_match_ref(power_law_ell, lanes, sem, frontier):
  """At the default budgets the extent-cut chunks give the reference's
  answer: exactly for min, within float rounding of the summation order
  for add."""
  n, g = power_law_ell
  rng = np.random.default_rng(11)
  shape = (n,) if lanes is None else (n, lanes)
  msg = jnp.asarray(rng.uniform(0.5, 5, shape).astype(np.float32))
  act = jnp.asarray(rng.uniform(size=n) < frontier)
  proc, kind = PROCS[sem]
  yk, rk = ell_spmv_pallas(g.cols, g.vals, g.mask, msg, act, process=proc,
                           reduce_kind=kind, slot_rows=g.slot_rows)
  yr, rr = ell_spmv_ref(g.cols, g.vals, g.mask, msg, act, process=proc,
                        reduce_kind=kind)
  np.testing.assert_array_equal(np.asarray(rk), np.asarray(rr))
  if kind == "min":
    np.testing.assert_array_equal(np.asarray(yk), np.asarray(yr))
  else:
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yr), rtol=1e-6)


# ---------------------------------------------------------------------------
# selective_scan kernel
# ---------------------------------------------------------------------------

from repro.kernels.selective_scan import selective_scan_pallas
from repro.kernels.ref_selective_scan import selective_scan_ref


@pytest.mark.parametrize("shape", [(1, 16, 8, 4), (2, 32, 16, 8),
                                   (2, 64, 32, 16)])
@pytest.mark.parametrize("chunks", [(8, 8), (16, 16)])
def test_selective_scan_matches_ref(shape, chunks):
  b, s, c, n = shape
  sc, ct = chunks
  sc, ct = min(sc, s), min(ct, c)
  rng = np.random.default_rng(hash((shape, chunks)) % 2**32)
  u = rng.standard_normal((b, s, c)).astype(np.float32)
  dt = (np.log1p(np.exp(rng.standard_normal((b, s, c)))) * 0.1
        ).astype(np.float32)
  a = -np.exp(rng.standard_normal((c, n))).astype(np.float32)
  bm = rng.standard_normal((b, s, n)).astype(np.float32)
  cm = rng.standard_normal((b, s, n)).astype(np.float32)
  yk = selective_scan_pallas(jnp.asarray(u), jnp.asarray(dt), jnp.asarray(a),
                             jnp.asarray(bm), jnp.asarray(cm),
                             seq_chunk=sc, c_tile=ct)
  yr = selective_scan_ref(jnp.asarray(u), jnp.asarray(dt), jnp.asarray(a),
                          jnp.asarray(bm), jnp.asarray(cm))
  np.testing.assert_allclose(np.asarray(yk), np.asarray(yr),
                             rtol=2e-4, atol=2e-5)


def test_mamba1_fused_matches_assoc():
  """Model-level: ssm_impl=fused == ssm_impl=assoc (falcon smoke)."""
  from repro import configs as C
  from repro.models.common import init_params
  from repro.models.transformer import build_model
  cfg_a = C.get_smoke_config("falcon_mamba_7b")
  cfg_f = cfg_a.scaled(ssm_impl="fused")
  m_a = build_model(cfg_a, tp=1)
  m_f = build_model(cfg_f, tp=1)
  params = init_params(m_a.defs(), jax.random.PRNGKey(0))
  toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                            cfg_a.vocab_size)
  la, _ = m_a.forward(params, {"tokens": toks})
  lf, _ = m_f.forward(params, {"tokens": toks})
  np.testing.assert_allclose(np.asarray(la, np.float32),
                             np.asarray(lf, np.float32),
                             rtol=2e-3, atol=2e-3)
