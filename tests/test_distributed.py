"""Distributed 2-D SpMV and engine on 8 fake host devices.

Runs in a SUBPROCESS because the fake-device count must be fixed before jax
initializes (and the rest of the suite must see exactly 1 device)."""

import json
import os
import subprocess
import sys

import pytest


_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, json
import jax, jax.numpy as jnp, numpy as np
from repro.algos.multi import multi_sssp_program
from repro.core import graph as G
from repro.core.distributed import (partition_2d, run_graph_program_2d,
                                    run_graph_program_2d_batched, spmv_2d)
from repro.core.engine import run_batched, run_graph_program
from repro.core.vertex_program import GraphProgram
from repro.graphs import rmat_edges, remove_self_loops, dedupe_edges

src, dst = rmat_edges(8, 8, seed=3)
src, dst = remove_self_loops(src, dst)
src, dst = dedupe_edges(src, dst)
n = 256
w = np.random.default_rng(0).uniform(0.1, 2.0, len(src)).astype(np.float32)

sssp = GraphProgram(process_message=lambda m, e, d: m + e, reduce_kind="min",
                    apply=lambda r, o: jnp.minimum(r, o),
                    process_reads_dst=False)

results = {}
for shape, axes in (((4, 2), ("data", "model")),
                    ((2, 2, 2), ("pod", "data", "model"))):
    mesh = jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
    R = int(np.prod(shape[:-1])); Cc = shape[-1]
    dg = partition_2d(src, dst, w, n=n, R=R, C=Cc)
    d0 = np.full(dg.n_pad, np.inf, np.float32); d0[3] = 0
    a0 = np.zeros(dg.n_pad, bool); a0[3] = True
    row_axes = axes[:-1]
    with jax.set_mesh(mesh):
        fin = run_graph_program_2d(dg, sssp, jnp.asarray(d0), jnp.asarray(a0),
                                   mesh, max_iters=300, row_axes=row_axes)
    coo = G.build_coo(src, dst, w, n=n)
    loc = run_graph_program(coo, sssp, jnp.asarray(d0[:n]),
                            jnp.asarray(a0[:n]), max_iters=300, backend="coo")
    ok = bool(np.allclose(np.asarray(fin.prop)[:n], np.asarray(loc.prop),
                          rtol=1e-5))
    results["x".join(map(str, shape))] = ok

# The 2-D runners run the engine's superstep: with and without the receive
# mask (needs_recv), single and batched, they match the local engine on the
# 4x2 mesh, answers and superstep counts alike.
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
dg = partition_2d(src, dst, w, n=n, R=4, C=2)
coo = G.build_coo(src, dst, w, n=n)
sources = np.array([3, 77, 130, 200])
q = len(sources)
d0 = np.full((dg.n_pad, q), np.inf, np.float32)
d0[sources, np.arange(q)] = 0
a0 = np.zeros((dg.n_pad, q), bool)
a0[sources, np.arange(q)] = True

def same(got, want):
    return bool(np.allclose(np.nan_to_num(np.asarray(got)[:n], posinf=1e30),
                            np.nan_to_num(np.asarray(want), posinf=1e30),
                            rtol=1e-5))

for needs_recv in (False, True):
    one = dataclasses.replace(sssp, needs_recv=needs_recv)
    lanes = dataclasses.replace(multi_sssp_program(), needs_recv=needs_recv)
    with jax.set_mesh(mesh):
        fin = run_graph_program_2d(dg, one, jnp.asarray(d0[:, 0]),
                                   jnp.asarray(a0[:, 0]), mesh, max_iters=300)
        finb = run_graph_program_2d_batched(dg, lanes, jnp.asarray(d0),
                                            jnp.asarray(a0), mesh,
                                            max_iters=300)
    loc = run_graph_program(coo, one, jnp.asarray(d0[:n, 0]),
                            jnp.asarray(a0[:n, 0]), max_iters=300,
                            backend="coo")
    locb = run_batched(coo, lanes, jnp.asarray(d0[:n]), jnp.asarray(a0[:n]),
                       max_iters=300, backend="coo")
    results[f"single_recv={needs_recv}"] = (
        same(fin.prop, loc.prop) and int(fin.iteration) == int(loc.iteration))
    results[f"batched_recv={needs_recv}"] = (
        same(finb.prop, locb.prop)
        and np.array_equal(np.asarray(finb.iters), np.asarray(locb.iters))
        and bool(np.asarray(finb.done).all()))
print(json.dumps(results))
"""


@pytest.mark.slow
def test_distributed_sssp_matches_local():
  env = dict(os.environ)
  env["PYTHONPATH"] = os.pathsep.join(
      [os.path.join(os.path.dirname(__file__), "..", "src"),
       env.get("PYTHONPATH", "")])
  res = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                       capture_output=True, text=True, timeout=900)
  assert res.returncode == 0, res.stderr[-3000:]
  results = json.loads(res.stdout.strip().splitlines()[-1])
  assert results == {"4x2": True, "2x2x2": True,
                     "single_recv=False": True, "single_recv=True": True,
                     "batched_recv=False": True,
                     "batched_recv=True": True}, results


_BATCHED_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp, numpy as np
from repro.algos import sssp
from repro.algos.multi import multi_sssp_program
from repro.core import graph as G
from repro.core.distributed import partition_2d, run_graph_program_2d_batched
from repro.graphs import rmat_edges, remove_self_loops, dedupe_edges

src, dst = rmat_edges(8, 8, seed=3)
src, dst = remove_self_loops(src, dst)
src, dst = dedupe_edges(src, dst)
n = 256
w = np.random.default_rng(0).uniform(0.1, 2.0, len(src)).astype(np.float32)

mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
dg = partition_2d(src, dst, w, n=n, R=4, C=2)
sources = np.array([3, 77, 130, 200], np.int32)
q = len(sources)
d0 = np.full((dg.n_pad, q), np.inf, np.float32)
a0 = np.zeros((dg.n_pad, q), bool)
d0[sources, np.arange(q)] = 0.0
a0[sources, np.arange(q)] = True
with jax.set_mesh(mesh):
    fin = run_graph_program_2d_batched(dg, multi_sssp_program(),
                                       jnp.asarray(d0), jnp.asarray(a0),
                                       mesh, max_iters=300,
                                       row_axes=("data",))
coo = G.build_coo(src, dst, w, n=n)
seq = np.stack([np.asarray(sssp(coo, int(s), n, backend="coo"))
                for s in sources], axis=1)
got = np.asarray(fin.prop)[:n]
ok = bool(np.allclose(np.nan_to_num(got, posinf=1e30),
                      np.nan_to_num(seq, posinf=1e30), rtol=1e-5))
all_done = bool(np.asarray(fin.done).all())
print("RESULT:" + json.dumps({"ok": ok, "all_done": all_done}))
"""


@pytest.mark.slow
def test_distributed_batched_multi_sssp_matches_local():
  """Query axis composes with the 2-D shard_map partitioning: batched
  distributed SSSP == per-source local runs."""
  env = dict(os.environ)
  env["PYTHONPATH"] = os.pathsep.join(
      [os.path.join(os.path.dirname(__file__), "..", "src"),
       env.get("PYTHONPATH", "")])
  res = subprocess.run([sys.executable, "-c", _BATCHED_CHILD], env=env,
                       capture_output=True, text=True, timeout=900)
  assert res.returncode == 0, res.stderr[-3000:]
  line = [l for l in res.stdout.splitlines() if l.startswith("RESULT:")][-1]
  out = json.loads(line[len("RESULT:"):])
  assert out == {"ok": True, "all_done": True}, out


_ELASTIC_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, tempfile
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.train.checkpoint import save_checkpoint, restore_checkpoint

# "Train" on an 8-device (4,2) mesh, checkpoint, restore onto (2,2) with
# different shardings — the elastic-resume path (mesh-agnostic host layout).
mesh_a = jax.make_mesh((4, 2), ("data", "model"),
                       axis_types=(jax.sharding.AxisType.Auto,) * 2)
mesh_b = jax.sharding.Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                           ("data", "model"))
w = jnp.arange(64 * 32, dtype=jnp.float32).reshape(64, 32)
sh_a = NamedSharding(mesh_a, P("data", "model"))
sh_b = NamedSharding(mesh_b, P("model", "data"))
w_a = jax.device_put(w, sh_a)
with tempfile.TemporaryDirectory() as d:
    save_checkpoint(d, 7, {"w": w_a})
    like = {"w": jnp.zeros_like(w)}
    restored = restore_checkpoint(d, 7, like, shardings={"w": sh_b})
ok = bool(np.array_equal(np.asarray(restored["w"]), np.asarray(w)))
resharded = restored["w"].sharding == sh_b
print("RESULT:" + json.dumps({"ok": ok, "resharded": bool(resharded)}))
"""


@pytest.mark.slow
def test_elastic_checkpoint_remesh():
  """Checkpoint written under mesh A restores bit-exact onto mesh B with
  different shape AND different PartitionSpecs (elastic re-scale)."""
  env = dict(os.environ)
  env["PYTHONPATH"] = os.pathsep.join(
      [os.path.join(os.path.dirname(__file__), "..", "src"),
       env.get("PYTHONPATH", "")])
  res = subprocess.run([sys.executable, "-c", _ELASTIC_CHILD], env=env,
                       capture_output=True, text=True, timeout=600)
  assert res.returncode == 0, res.stderr[-3000:]
  line = [l for l in res.stdout.splitlines() if l.startswith("RESULT:")][-1]
  out = json.loads(line[len("RESULT:"):])
  assert out == {"ok": True, "resharded": True}
