"""Per-kernel allclose sweeps: Pallas (interpret) vs ref.py oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ell_spmv import ell_spmv_pallas
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
  then gathers only within them (in many small chunks here), matches the
  full-width reference."""
  from repro.core.graph import build_ell
  from repro.kernels import ell_spmv
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
