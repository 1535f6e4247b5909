"""Compile the main path for a TPU v5e from shapes alone (no chip needed).

The TPU compiler ships with jaxlib and compiles for a described, unattached
v5e: these tests catch what interpret mode cannot (Mosaic tiling rules, VMEM
limits, programs that do not fit HBM) at the shapes of a Graph500 scale-22
graph.  Nothing runs, so they say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and pytest workers import every test file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import graph as G
from repro.core.backends import Plan
from repro.kernels.ell_spmv import ell_spmv_pallas

# Graph500 scale 22, edge factor 16, seed 0 (graphs.rmat_edges with
# RMAT_PRBFS), self loops removed and deduplicated; the ELL width is
# build_ell's default (95th-percentile in-degree).  The 32,466,159 edges of
# rows above it fill about 254K split rows, and the partial last pieces of
# the ~110K hub rows (5% of the rows that hold an edge) add about 90K more.
N = 1 << 22
EDGES = 65_622_448
ELL_WIDTH = 128
SPLIT_ROWS = 344_000
N_PAD = -(-(N + SPLIT_ROWS) // 128) * 128
QUERIES = 8
HBM_BYTES = 16 * 2**30
KERNEL_MARK = "tpu_custom_call"


def _slot_rows():
  """build_ell's row extents per slot, home rows = 0.52 N (1 + s)^-0.7 (a
  power law fit to the exact extents at scale 20: 52% of rows hold an edge,
  2.3% more than 88) plus the split rows, rounded up to 128."""
  return tuple(min(N_PAD, -(-int(0.52 * N * (1 + s) ** -0.7 + SPLIT_ROWS)
                            // 128) * 128)
               for s in range(ELL_WIDTH))


@pytest.fixture(scope="module")
def topo():
  from jax.experimental import topologies
  from jax.experimental.compilation_cache import compilation_cache
  os.environ.setdefault("TPU_LOG_DIR", "disabled")
  was = jax.config.jax_enable_compilation_cache
  # A compile for a described chip is written to the persistent cache but
  # cannot be read back without one: keep the cache out of it.
  jax.config.update("jax_enable_compilation_cache", False)
  compilation_cache.reset_cache()
  try:
    desc = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
  except Exception as e:
    jax.config.update("jax_enable_compilation_cache", was)
    pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
  yield desc
  jax.config.update("jax_enable_compilation_cache", was)
  compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
  """``spec(shape, dtype)``: an abstract array on one described v5e."""
  one_chip = SingleDeviceSharding(topo.devices[0])
  return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                   sharding=one_chip)


def _coo(spec, edges):
  return G.CooGraph(N, spec((edges,), jnp.int32), spec((edges,), jnp.int32),
                    spec((edges,), jnp.float32), spec((edges,), jnp.bool_),
                    spec((N,), jnp.int32), spec((N,), jnp.int32))


def _largest_array(tree):
  """Bytes of the largest array of ``tree`` (jit drops unused arguments, so
  the compiled arguments hold at least this much of a graph it reads)."""
  return max(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(tree))


def _fits(compiled):
  m = compiled.memory_analysis()
  used = m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes
  assert used < HBM_BYTES, f"{used / 2**30:.2f} GiB does not fit one v5e"
  return m


@pytest.mark.parametrize("lanes", [None, QUERIES], ids=["scalar", "q8"])
def test_ell_kernel_compiles_at_scale_22(spec, lanes):
  """The Pallas ELL kernel is compiled by Mosaic (not interpreted) at the
  scale-22 ELL shapes, for scalar messages and for the Q=8 query lanes."""
  msg_shape = (N,) if lanes is None else (N, lanes)

  def step(cols, vals, mask, msg, active):
    return ell_spmv_pallas(cols, vals, mask, msg, active,
                           process=lambda m, e, d: m + 1, reduce_kind="min",
                           slot_rows=_slot_rows())

  compiled = jax.jit(step).lower(
      spec((ELL_WIDTH, N_PAD), jnp.int32),
      spec((ELL_WIDTH, N_PAD), jnp.float32),
      spec((ELL_WIDTH, N_PAD), jnp.bool_), spec(msg_shape, jnp.int32),
      spec((N,), jnp.bool_)).compile()
  assert KERNEL_MARK in compiled.as_text()
  # Its operands are pinned to HBM, so the kernel's own time covers reading
  # them (XLA would otherwise stage small chunks into VMEM outside it).  An
  # unpinned call lists no colors: ``"input_memory_space_colors":[]``.
  assert '"input_memory_space_colors":[{' in compiled.as_text()
  # The message gather runs in bounded chunks: no [W, n_pad] temporary.
  assert _fits(compiled).temp_size_in_bytes < 2 * 2**30


def test_server_round_takes_graph_as_argument(spec, rmat_small):
  """The server's jitted round takes the graph as an argument: a server
  built on a small graph lowers its round for the scale-22 COO shapes, and
  the graph's arrays are program arguments, not constants."""
  from repro.core.engine import init_batched_state
  from repro.service import BfsFamily, GraphQueryServer

  n, src, dst, _ = rmat_small
  server = GraphQueryServer(G.build_coo(src, dst, n=n), BfsFamily(n),
                            num_slots=QUERIES, backend=Plan(backend="coo"))
  server.close()
  graph = _coo(spec, EDGES)
  state = jax.eval_shape(lambda: init_batched_state(
      jnp.zeros((N, QUERIES), jnp.int32), jnp.zeros((N, QUERIES), bool)))
  state = jax.tree_util.tree_map(lambda x: spec(x.shape, x.dtype), state)
  compiled = server._round_fn.lower(graph, state).compile()
  assert _fits(compiled).argument_size_in_bytes >= _largest_array(graph)


@pytest.mark.parametrize("fmt", ["coo", "ell"])
def test_bfs_compiles_at_scale_22(spec, fmt):
  """``algos.bfs`` compiles for v5e on the scale-22 graph; on ELL through
  the compiled Pallas kernel."""
  from repro.algos import bfs
  if fmt == "coo":
    graph = _coo(spec, EDGES)
  else:
    graph = G.EllGraph(
        N, ELL_WIDTH, spec((ELL_WIDTH, N_PAD), jnp.int32),
        spec((ELL_WIDTH, N_PAD), jnp.float32),
        spec((ELL_WIDTH, N_PAD), jnp.bool_),
        spec((N_PAD,), jnp.int32), spec((N,), jnp.int32),
        spec((SPLIT_ROWS,), jnp.int32), spec((SPLIT_ROWS,), jnp.int32),
        _slot_rows())
  plan = Plan(backend="coo" if fmt == "coo" else "pallas")
  compiled = jax.jit(lambda g, r: bfs(g, r, N, backend=plan)).lower(
      graph, spec((), jnp.int32)).compile()
  assert (KERNEL_MARK in compiled.as_text()) == (fmt == "ell")
  assert _fits(compiled).argument_size_in_bytes >= _largest_array(graph)
