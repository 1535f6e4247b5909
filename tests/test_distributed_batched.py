"""Distributed batched (query-axis) engine on a 4-device CPU mesh.

Exercises ``run_graph_program_2d_batched`` — the SpMM over a 2-D
block-partitioned mesh — against the local ``run_batched`` engine, closing
the ROADMAP item "exercise run_graph_program_2d_batched in tests on a
multi-device mesh".  Runs in a SUBPROCESS because
``--xla_force_host_platform_device_count`` must be set before jax
initializes (and the rest of the suite must see exactly 1 device).
"""

import json
import os
import subprocess
import sys

import pytest


_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax, jax.numpy as jnp, numpy as np
from repro.algos.bfs import UNREACHED
from repro.algos.multi import (bfs_columns, multi_bfs_program,
                               multi_sssp_program, sssp_columns)
from repro.core import graph as G
from repro.core.distributed import (partition_2d, pad_vertex_tree,
                                    run_graph_program_2d_batched)
from repro.core.engine import run_batched
from repro.graphs import rmat_edges, remove_self_loops, dedupe_edges

assert len(jax.devices()) == 4, jax.devices()

src, dst = rmat_edges(8, 8, seed=3)
src, dst = remove_self_loops(src, dst)
src, dst = dedupe_edges(src, dst)
n = 256
w = np.random.default_rng(0).uniform(0.1, 2.0, len(src)).astype(np.float32)

mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
sources = jnp.asarray(np.array([3, 77, 130, 200], np.int32))
out = {}

# BFS: int32 hops, so distributed == local must be *exact*.
dg = partition_2d(src, dst, n=n, R=2, C=2)
d0, a0 = bfs_columns(sources, n)
d0p = pad_vertex_tree(d0, n, dg.n_pad, fill=UNREACHED)
a0p = pad_vertex_tree(a0, n, dg.n_pad, fill=False)
with jax.set_mesh(mesh):
    fin = run_graph_program_2d_batched(dg, multi_bfs_program(), d0p, a0p,
                                       mesh, max_iters=300,
                                       row_axes=("data",))
loc = run_batched(G.build_coo(src, dst, n=n), multi_bfs_program(), d0, a0,
                  max_iters=300, backend="coo")
out["bfs_exact"] = bool(
    np.array_equal(np.asarray(fin.prop)[:n], np.asarray(loc.prop)))
out["bfs_done"] = bool(np.asarray(fin.done).all()
                       and np.asarray(loc.done).all())
out["bfs_iters"] = bool(
    np.array_equal(np.asarray(fin.iters), np.asarray(loc.iters)))

# Weighted SSSP: float path, compare to tolerance.
dgw = partition_2d(src, dst, w, n=n, R=2, C=2)
s0, sa0 = sssp_columns(sources, n)
s0p = pad_vertex_tree(s0, n, dgw.n_pad, fill=np.inf)
sa0p = pad_vertex_tree(sa0, n, dgw.n_pad, fill=False)
with jax.set_mesh(mesh):
    finw = run_graph_program_2d_batched(dgw, multi_sssp_program(), s0p, sa0p,
                                        mesh, max_iters=300,
                                        row_axes=("data",))
locw = run_batched(G.build_coo(src, dst, w, n=n), multi_sssp_program(),
                   s0, sa0, max_iters=300, backend="coo")
got = np.nan_to_num(np.asarray(finw.prop)[:n], posinf=1e30)
ref = np.nan_to_num(np.asarray(locw.prop), posinf=1e30)
out["sssp_close"] = bool(np.allclose(got, ref, rtol=1e-5))
out["sssp_done"] = bool(np.asarray(finw.done).all())
print("RESULT:" + json.dumps(out))
"""


@pytest.mark.slow
def test_distributed_batched_matches_run_batched():
  """The query axis composes with the 2-D mesh partitioning: a 4-device
  ``run_graph_program_2d_batched`` reproduces local ``run_batched`` —
  bitwise for int BFS (hops and per-query iters), to fp tolerance for
  weighted SSSP."""
  env = dict(os.environ)
  env["PYTHONPATH"] = os.pathsep.join(
      [os.path.join(os.path.dirname(__file__), "..", "src"),
       env.get("PYTHONPATH", "")])
  res = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                       capture_output=True, text=True, timeout=900)
  assert res.returncode == 0, res.stderr[-3000:]
  line = [l for l in res.stdout.splitlines() if l.startswith("RESULT:")][-1]
  out = json.loads(line[len("RESULT:"):])
  assert out == {"bfs_exact": True, "bfs_done": True, "bfs_iters": True,
                 "sssp_close": True, "sssp_done": True}, out
