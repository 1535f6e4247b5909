"""Paper Fig. 5 analogue: scaling with parallel workers.

The 2015 paper scales OpenMP threads on a 24-core Xeon.  This host has ONE
core, so wall-clock "scaling" is meaningless here; what we CAN measure
faithfully is the thing that *determines* scaling on the target machine:
per-device work and collective traffic of the distributed (shard_map)
GraphMat engine as the mesh grows.  For each device count D we lower the
distributed PageRank superstep on a (D×1) host mesh, run the trip-count-
aware HLO analyzer, and report the roofline-projected speedup on TPU-v5e
constants (197 TF bf16, 819 GB/s HBM, 50 GB/s ICI) plus the measured
per-device balance.  The projection runs in a child process on fake CPU
devices (``JAX_PLATFORMS=cpu``).  Run standalone:

  PYTHONPATH=src python benchmarks/bench_scaling.py
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9

_CHILD = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys
import jax, jax.numpy as jnp, numpy as np
from repro.core import graph as G
from repro.core.distributed import partition_2d, spmv_2d
from repro.algos.pagerank import pagerank_program
from repro.graphs import rmat_edges, remove_self_loops, dedupe_edges, shuffle_vertices
from repro.graphs.rmat import RMAT_PRBFS
from repro.analysis.hlo_cost import analyze

scale, ef = 14, 8
src, dst = rmat_edges(scale, ef, RMAT_PRBFS, seed=9)
src, dst = remove_self_loops(src, dst)
src, dst = dedupe_edges(src, dst)
n = 1 << scale
src, dst, _ = shuffle_vertices(src, dst, n, seed=2)
prog = pagerank_program()
out = []
# 1-D row partitioning (the paper's layout: message vector effectively
# shared) vs 2-D blocks (CombBLAS layout, our beyond-paper distribution).
for tag, (r, c) in (("1d_1", (1, 1)), ("1d_2", (2, 1)), ("1d_4", (4, 1)),
                    ("1d_8", (8, 1)), ("2d_4", (2, 2)), ("2d_8", (4, 2))):
    mesh = jax.make_mesh((r, c), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    dg = partition_2d(src, dst, None, n=n, R=r, C=c)
    msg = jnp.ones((dg.n_pad,), jnp.float32)
    act = jnp.ones((dg.n_pad,), bool)
    prop = {"rank": msg, "deg": msg}
    def one(msg, act, prop):
        return spmv_2d(dg, msg, act, prop, prog, mesh)
    with jax.set_mesh(mesh):
        lowered = jax.jit(one).lower(msg, act, prop)
        compiled = lowered.compile()
    acc = analyze(compiled.as_text())
    coll = sum(v["bytes"] for v in acc["collectives"].values())
    pop = np.asarray(jnp.sum(dg.emask, axis=-1), np.float64)
    out.append(dict(tag=tag, devices=r * c, flops=acc["flops"],
                    bytes=acc["bytes"], coll_bytes=coll,
                    balance=float(pop.max() / max(pop.mean(), 1.0))))
print(json.dumps(out))
"""


def main() -> list:
  # The child is an HLO projection on fake host devices: it must never open
  # an accelerator, which its parent may already hold.
  env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
  res = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                       capture_output=True, text=True, timeout=900)
  if res.returncode != 0:
    raise RuntimeError(f"scaling child failed: {res.stderr.strip()[-2000:]}")
  data = json.loads(res.stdout.strip().splitlines()[-1])
  rows = []
  t1 = None
  for rec in data:
    t = max(rec["flops"] / PEAK_FLOPS, rec["bytes"] / HBM_BW,
            rec["coll_bytes"] / ICI_BW)
    t1 = t1 if t1 is not None else t * rec["devices"]  # D=1 total
    speedup = (t1 / t) if t > 0 else float("nan")
    rows.append(
        f"scaling/pagerank_{rec['tag']},{t*1e6:.2f},"
        f"projected_speedup={speedup:.2f}x balance={rec['balance']:.2f} "
        f"coll_bytes={rec['coll_bytes']:.2e} bytes={rec['bytes']:.2e}")
  return rows


if __name__ == "__main__":
  for r in main():
    print(r)
