"""chip_smoke.py's phases on the CPU at Graph500 scale 10.

The phases run against the same scipy references as on the chip; the ELL
plan's Pallas kernel runs in interpret mode here (the platform is the CPU).
The TPU check lives in ``main()`` alone, which must refuse to run here.
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro import compile_cache  # noqa: E402

SCALE = 10


def test_one_chip_phases_match_scipy():
  records = chip_smoke.one_chip_phases(SCALE, seed=0)
  phases = [r["phase"] for r in records]
  assert phases == ["reference",
                    "coo/pagerank", "coo/bfs", "coo/sssp",
                    "ell/pagerank", "ell/bfs", "ell/sssp",
                    "coo/server", "ell/server"]
  by = {r["phase"]: r for r in records}
  assert all(by[f"ell/{a}"]["plan"] == "pallas"
             for a in ("pagerank", "bfs", "sssp"))
  assert by["ell/server"]["plan"] == "pallas"
  assert by["ell/bfs"]["exact"] and by["coo/server"]["exact"]
  assert by["ell/server"]["queries"] == chip_smoke.NUM_ROOTS
  # No TPU here: nothing may claim a compiled Mosaic kernel.
  assert not any(r.get("kernel") for r in records)


def test_graph_is_graph500_shaped():
  n, src, dst, w = chip_smoke.make_edges(SCALE, seed=0)
  assert n == 1 << SCALE
  assert src.size == dst.size == w.size
  # Deduplication drops about a quarter of the edges at this scale.
  assert 0.5 * chip_smoke.EDGE_FACTOR * n < src.size <= (
      chip_smoke.EDGE_FACTOR * n)
  assert not (src == dst).any()                       # no self loops
  assert len(set(zip(src.tolist(), dst.tolist()))) == src.size  # deduped
  assert ((w >= 1.0) & (w < 2.0)).all()


def _rmat_edges_loop(scale, edge_factor, abc, seed, noise=0.1):
  """The generator as first written (int64 accumulators, np.where)."""
  a, b, c = abc
  n_edges = (1 << scale) * edge_factor
  rng = np.random.default_rng(seed)
  src = np.zeros(n_edges, np.int64)
  dst = np.zeros(n_edges, np.int64)
  for level in range(scale):
    f = 1.0 + noise * (2 * rng.random(4) - 1.0)
    pa, pb, pc, pd = a * f[0], b * f[1], c * f[2], (1 - a - b - c) * f[3]
    norm = pa + pb + pc + pd
    pa, pb, pc = pa / norm, pb / norm, pc / norm
    u = rng.random(n_edges)
    src_bit = (u >= pa + pb).astype(np.int64)
    dst_bit = np.where(src_bit == 0, (u >= pa).astype(np.int64),
                       (u >= pa + pb + pc).astype(np.int64))
    src |= src_bit << level
    dst |= dst_bit << level
  return src.astype(np.int32), dst.astype(np.int32)


@pytest.mark.parametrize("seed", [0, 3])
def test_rmat_edges_match_reference_generator(seed):
  from repro.graphs import rmat_edges
  from repro.graphs.rmat import RMAT_PRBFS, RMAT_TC
  for abc in (RMAT_PRBFS, RMAT_TC):
    got = rmat_edges(SCALE, 16, abc, seed=seed)
    want = _rmat_edges_loop(SCALE, 16, abc, seed)
    for g, w in zip(got, want):
      assert g.dtype == np.int32
      np.testing.assert_array_equal(g, w)


def test_reference_bfs_levels_match_unweighted_dijkstra():
  from scipy.sparse import csgraph
  n, src, dst, w = chip_smoke.make_edges(8, seed=1)
  ref = chip_smoke.Reference(n, src, dst, w)
  root = chip_smoke.pick_roots(ref, seed=1)[0]
  hops = csgraph.dijkstra(ref.fwd, directed=True, indices=root,
                          unweighted=True)
  got = ref.bfs(root)
  reached = hops < float("inf")
  assert (got[reached] == hops[reached]).all()
  assert (got[~reached] == int(chip_smoke.UNREACHED)).all()


def test_main_refuses_without_tpu(capsys):
  assert jax.devices()[0].platform != "tpu"
  assert chip_smoke.main(["--scale", str(SCALE)]) != 0
  out = capsys.readouterr().out
  assert '"ok"' not in out


def test_four_chip_phases_on_four_host_devices():
  """The --four-chips path (2x2 mesh, 2-D runner vs one device) on four
  virtual CPU devices; a subprocess, since the device count is fixed before
  JAX starts."""
  child = (
      "import os, sys\n"
      "os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'\n"
      f"sys.path.insert(0, {ROOT!r})\n"
      "import chip_smoke\n"
      f"recs = chip_smoke.four_chip_phases({SCALE}, seed=0)\n"
      "print('RESULT ' + ' '.join(r['phase'] for r in recs))\n")
  env = dict(os.environ, JAX_PLATFORMS="cpu")
  res = subprocess.run([sys.executable, "-c", child], env=env,
                       capture_output=True, text=True, timeout=600)
  assert res.returncode == 0, res.stderr[-3000:]
  line = [l for l in res.stdout.splitlines() if l.startswith("RESULT ")][-1]
  assert line.split()[1:] == ["2d/pagerank", "2d/bfs"]


def test_compile_cache_dir_honours_env(monkeypatch, tmp_path):
  monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
  assert compile_cache.compile_cache_dir() == str(tmp_path)
  monkeypatch.delenv(compile_cache.ENV_VAR)
  path = compile_cache.compile_cache_dir()
  assert path == str(compile_cache.CHECKOUT_CACHE_DIR)
  assert os.path.samefile(os.path.dirname(path), ROOT)
  assert os.path.basename(path) == ".jax_cache"


@pytest.mark.parametrize("env_set", [True, False])
def test_enable_compile_cache_sets_one_directory(monkeypatch, tmp_path,
                                                 env_set):
  """With the variable set, JAX reads it and no other directory is set;
  without it, the fixed in-checkout path is set.  (jax.config is recorded,
  not changed: tests leave the cache off.)"""
  calls = []
  monkeypatch.setattr(jax.config, "update",
                      lambda name, value: calls.append((name, value)))
  if env_set:
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
  else:
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
  path = compile_cache.enable_compile_cache()
  dirs = [v for k, v in calls if k == "jax_compilation_cache_dir"]
  assert ("jax_enable_compilation_cache", True) in calls
  if env_set:
    assert path == str(tmp_path) and dirs == []
  else:
    assert dirs == [str(compile_cache.CHECKOUT_CACHE_DIR)] == [path]


def test_last_line_is_the_result_object(monkeypatch, capsys):
  """On a TPU, main() ends with exactly the result object (phases stubbed;
  the device is faked as a TPU)."""

  class FakeTpu:
    platform, device_kind = "tpu", "TPU v5 lite"

    def memory_stats(self):
      return {"peak_bytes_in_use": 1}

  monkeypatch.setattr(chip_smoke.jax, "devices", lambda: [FakeTpu()])
  monkeypatch.setattr(chip_smoke, "enable_compile_cache", lambda: "cache")
  monkeypatch.setattr(chip_smoke, "one_chip_phases",
                      lambda scale, seed: [{"phase": "ell/bfs",
                                            "kernel": True}])
  assert chip_smoke.main([]) == 0
  last = capsys.readouterr().out.strip().splitlines()[-1]
  assert json.loads(last) == {"ok": True, "device": {
      "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
