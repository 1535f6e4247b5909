#!/usr/bin/env python3
"""Record ``scoped.xplane.pb``: a profile, with the program's HLO, of one
PageRank run through the Pallas ELL path and of served SSSP rounds through
``GraphQueryServer`` on COO, both on one RMAT SCALE-10 graph (Graph500's
quadrant probabilities, drawn on the host so that no generator program's HLO
enters the profile).

  python3 bench/tests/data/record_scoped.py OUT_DIR

Run it on a TPU; it writes ``OUT_DIR/scoped.xplane.pb``, which
``bench/tests/test_scopes.py`` reads.  The window is the benchmark's
``bench.window`` span, the PageRank run its ``bench.run``.
"""

import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

SCALE = 10
SEED = 2100000001
SLOTS = 8
SWEEPS = 4


KEEP = ("graphmat.", "bench.")   # the host spans the readings use


def _varint(n: int) -> bytes:
  out = bytearray()
  while True:
    b, n = n & 0x7F, n >> 7
    out.append(b | (0x80 if n else 0))
    if not n:
      return bytes(out)


def _encode(fields) -> bytes:
  """Wire format of ``(field, value)`` pairs as ``bench.scopes._fields``
  yields them, for messages with varint and length-delimited fields only
  (``XSpace``, ``XPlane``, ``XLine``: their fixed-width numbers sit in
  nested messages, which pass through as bytes)."""
  out = bytearray()
  for num, value in fields:
    if isinstance(value, int):
      out += _varint(num << 3) + _varint(value)
    else:
      out += _varint(num << 3 | 2) + _varint(len(value)) + value
  return bytes(out)


def keep_spans(space: bytes) -> bytes:
  """The profile with each host thread's events cut to the spans the
  readings use (``KEEP``), which keeps the file small; device planes and
  the HLO are left whole."""
  from bench.scopes import _fields, _first
  planes = []
  for f, plane in _fields(space):
    name = _first(plane, 2).decode() if f == 1 else ""
    if f != 1 or not name.startswith("/host:") or name == "/host:metadata":
      planes.append((f, plane))
      continue
    keep = {_first(entry, 1, 0) for g, entry in _fields(plane) if g == 4
            and _first(_first(entry, 2), 2).decode().startswith(KEEP)}
    fields = []
    for g, v in _fields(plane):
      if g == 3:                                   # a line: keep its spans
        line = [(h, x) for h, x in _fields(v)
                if h != 4 or _first(x, 1, 0) in keep]
        if not any(h == 4 for h, _ in line):
          continue
        v = _encode(line)
      fields.append((g, v))
    planes.append((f, _encode(fields)))
  return _encode(planes)


def main(out_dir: str) -> None:
  import jax
  import jax.numpy as jnp
  import numpy as np

  # Compiled here, with the scopes: no program from a cache made before them.
  jax.config.update("jax_enable_compilation_cache", False)

  from bench import scopes
  from bench.harness import RUN_SPAN, WINDOW_SPAN
  from repro.algos.pagerank import pagerank
  from repro.core import graph as G
  from repro.core.backends import Plan
  from repro.graphs import remove_self_loops, rmat_edges, symmetrize
  from repro.service import GraphQueryServer, QuerySpec, SsspFamily

  n = 1 << SCALE
  src, dst = rmat_edges(SCALE, 16, abc=(0.57, 0.19, 0.19), seed=SEED,
                        noise=0.0)
  src, dst = symmetrize(*remove_self_loops(src, dst))
  w = np.random.default_rng(SEED).random(src.size, dtype=np.float32)
  ell = G.build_ell(src, dst, w, n=n)
  coo = G.build_coo(src, dst, w, n=n)
  out_deg = np.bincount(src, minlength=n)
  deg = jnp.asarray(out_deg, jnp.float32)
  plan = Plan(backend="pallas")
  jax.block_until_ready(pagerank(ell, deg, num_iters=SWEEPS, backend=plan))

  server = GraphQueryServer(coo, SsspFamily(n), num_slots=SLOTS,
                            steps_per_round=4, backend=Plan(backend="coo"))
  sources = np.flatnonzero(out_deg > 0)
  for s in sources[:SLOTS]:                     # compiles every program
    server.submit(QuerySpec("sssp", int(s)))
  server.drain()
  for s in sources[SLOTS:3 * SLOTS]:
    server.submit(QuerySpec("sssp", int(s)))

  log_dir = tempfile.mkdtemp(prefix="scoped-")
  opts = scopes.profiler_options()
  opts.host_tracer_level = 1          # the spans, not the runtime's own
  jax.profiler.start_trace(log_dir, profiler_options=opts)
  try:
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
      with jax.profiler.TraceAnnotation(RUN_SPAN):
        jax.block_until_ready(pagerank(ell, deg, num_iters=SWEEPS,
                                       backend=plan))
      for _ in range(3):
        server.step_round()
  finally:
    jax.profiler.stop_trace()
  out = pathlib.Path(out_dir)
  out.mkdir(parents=True, exist_ok=True)
  from bench.trace import find_xplane
  with open(find_xplane(log_dir), "rb") as f:
    space = f.read()
  (out / "scoped.xplane.pb").write_bytes(keep_spans(space))
  shutil.rmtree(log_dir, ignore_errors=True)
  print(f"wrote {out / 'scoped.xplane.pb'}; "
        f"{server.stats()['counters'].get('slots.retired', 0):.0f} retired")


if __name__ == "__main__":
  main(sys.argv[1])
