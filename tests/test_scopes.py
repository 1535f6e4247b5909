"""Named scopes on the device and spans on the host.

Each superstep phase runs under a ``jax.named_scope`` below ``graphmat/``,
so the compiled program's ``op_name`` metadata (and with it a profile) says
which phase a device operation belongs to.  A served round records host
spans (``repro.service.metrics.SPAN_*``) around admission, the supersteps,
retirement and every device-to-host fetch.
"""

import dataclasses
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.algos.multi import multi_sssp_program
from repro.core import engine
from repro.core import graph as G
from repro.core.backends import Plan
from repro.service import GraphQueryServer, QuerySpec, SsspFamily
from repro.service import metrics as M

ELL_PHASES = {"send", "spmv/gather", "spmv/kernel", "spmv/unpermute",
              "spmv/split", "apply"}
COO_PHASES = {"send", "spmv/gather", "spmv/scatter", "apply"}
# Instructions that do no work of a phase: loop plumbing and layout.
CONTROL = {"parameter", "get-tuple-element", "tuple", "constant", "copy",
           "bitcast", "while", "conditional", "call"}

_COMP = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (\S+) ([\w\-]+)\((.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_PHASE = re.compile(r"graphmat/(send|apply|install|extract|spmv/\w+)")


def _computations(text):
  comps, cur = {}, None
  for line in text.splitlines():
    m = _COMP.match(line)
    if m:
      cur = comps.setdefault(m.group(1), [])
    elif cur is not None and _INSTR.match(line):
      cur.append(line)
  return comps


def _phase(line):
  """The outermost ``graphmat/`` phase of an instruction, or None."""
  name = _OP_NAME.search(line)
  phase = _PHASE.search(name.group(1)) if name else None
  return phase.group(1) if phase else None


def _loop_work(text):
  """The instructions that do work in the loop bodies of a compiled module,
  and in what they call (not the insides of fusions): everything but
  control, scalar loop bookkeeping, materialized constants, and what the
  compiler made with no ``op_name`` of the program's."""
  comps = _computations(text)
  todo = re.findall(r"body=%?([\w.\-]+)", text)
  seen, work = set(), []
  while todo:
    comp = todo.pop()
    if comp in seen:
      continue
    seen.add(comp)
    for line in comps[comp]:
      _, shape, opcode, rest = _INSTR.match(line).groups()
      if opcode in ("while", "call", "conditional"):
        todo += re.findall(r"(?:body|to_apply|calls)=%?([\w.\-]+)", rest)
        for group in re.findall(r"branch_computations=\{([^}]*)\}", rest):
          todo += [b.strip().lstrip("%") for b in group.split(",")]
      if (opcode in CONTROL or re.match(r"^\w+\[\]", shape)
          or not _OP_NAME.search(line)):
        continue
      operands = re.findall(r"%([\w.\-]+)", rest.split(")")[0])
      if operands and all(o.startswith("constant") for o in operands):
        continue
      work.append(line)
  return work


def _check_scoped(compiled_text, phases):
  work = _loop_work(compiled_text)
  assert work, "no loop body found"
  unscoped = [w.strip()[:160] for w in work if _phase(w) is None]
  assert not unscoped, unscoped
  named = {_phase(line) for lines in _computations(compiled_text).values()
           for line in lines} - {None}
  assert named == phases


@pytest.fixture(scope="module")
def graphs(rmat_small):
  n, src, dst, w = rmat_small
  ell = G.build_ell(src, dst, w, n=n)
  assert len(ell.split_rows) > 0      # the split fold's phase is on the path
  return n, src, {"ell": ell, "pallas": ell,
                  "coo": G.build_coo(src, dst, w, n=n)}


PATHS = [("pallas", ELL_PHASES), ("ell", ELL_PHASES), ("coo", COO_PHASES)]


@pytest.mark.parametrize("backend,phases", PATHS)
def test_pagerank_loop_is_scoped(graphs, backend, phases):
  from repro.algos.pagerank import _pagerank_jit
  n, src, g = graphs
  deg = jnp.asarray(np.bincount(src, minlength=n).astype(np.float32))
  text = _pagerank_jit.lower(
      g[backend], deg, num_iters=3, r=0.15, tol=0.0,
      backend=Plan(backend=backend)).compile().as_text()
  _check_scoped(text, phases)


@pytest.mark.parametrize("backend,phases", PATHS)
def test_sssp_loop_is_scoped(graphs, backend, phases):
  from repro.algos.sssp import _sssp_jit
  n, _, g = graphs
  text = _sssp_jit.lower(g[backend], jnp.int32(0), n=n,
                         backend=Plan(backend=backend),
                         max_iters=100).compile().as_text()
  # SSSP sends its distance as it is: SEND_MESSAGE compiles to nothing.
  _check_scoped(text, phases - {"send"})


def test_cdlp_loop_is_scoped(graphs):
  """CDLP's mode reduce (sort, runs, arg-max) runs under its own scope."""
  from repro.algos.cdlp import _cdlp_jit
  text = _cdlp_jit.lower(graphs[2]["coo"], num_iters=3,
                         backend=Plan(backend="coo")).compile().as_text()
  # CDLP sends its label as it is: SEND_MESSAGE compiles to nothing.
  _check_scoped(text, {"spmv/gather", "spmv/mode", "apply"})


@pytest.mark.parametrize("backend,phases", PATHS)
def test_batched_rounds_loop_is_scoped(graphs, backend, phases):
  n, _, g = graphs
  q = 4
  dist = jnp.full((n, q), jnp.inf).at[jnp.arange(q), jnp.arange(q)].set(0.0)
  active = jnp.zeros((n, q), bool).at[jnp.arange(q), jnp.arange(q)].set(True)
  state = engine.init_batched_state(dist, active)
  prog = multi_sssp_program()
  fn = jax.jit(lambda gr, st: engine.run_batched_rounds(
      gr, prog, st, 4, backend=Plan(backend=backend)))
  _check_scoped(fn.lower(g[backend], state).compile().as_text(), phases)


@pytest.mark.parametrize("runner", ["run_graph_program_2d",
                                    "run_graph_program_2d_batched"])
def test_2d_runner_loop_is_scoped(rmat_small, runner):
  """The 2-D runners run the engine's superstep, so SEND and APPLY carry
  their scopes on the mesh as well."""
  from repro.algos.pagerank import init_prop, pagerank_program
  from repro.core import distributed as D
  from repro.core.vertex_program import lanewise_activate
  n, src, dst, w = rmat_small
  mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                           ("data", "model"))
  g = D.partition_2d(src, dst, w, n=n, R=1, C=1, mesh=mesh)
  prog = pagerank_program()
  prop = init_prop(jnp.asarray(np.bincount(src, minlength=n), jnp.float32))
  active = jnp.ones((n,), bool)
  if runner.endswith("batched"):
    prog = dataclasses.replace(prog, activate=lanewise_activate)
    prop, active = jax.tree_util.tree_map(lambda x: x[:, None],
                                          (prop, active))
  fn = jax.jit(lambda gr, p, a: getattr(D, runner)(
      gr, prog, p, a, mesh, max_iters=3))
  text = fn.lower(g, prop, active).compile().as_text()
  named = {_phase(line) for lines in _computations(text).values()
           for line in lines}
  assert {"send", "apply", "spmv/gather", "spmv/scatter"} <= named, named


# ---------------------------------------------------------------------------
# Host spans of a served round
# ---------------------------------------------------------------------------


def _server(n, src, dst, w):
  g = G.build_coo(src, dst, w, n=n)
  return GraphQueryServer(g, SsspFamily(n), num_slots=4, steps_per_round=2,
                          backend="coo")


def _sources(n, src):
  """Two sources with out-arcs and one without, which converges at once."""
  deg = np.bincount(src, minlength=n)
  alone = int(np.flatnonzero(deg == 0)[0])
  busy = [int(v) for v in np.argsort(-deg)[:2]]
  return busy + [alone]


def _host_spans(log_dir):
  from jax.profiler import ProfileData
  path, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
  spans = []
  for plane in ProfileData.from_file(path).planes:
    if plane.name.startswith("/host:"):
      for line in plane.lines:
        spans += [(e.name, e.start_ns, e.end_ns, dict(e.stats))
                  for e in line.events if e.name.startswith("graphmat.")]
  return spans


def test_round_records_its_spans(rmat_small, tmp_path):
  n, src, dst, w = rmat_small
  server, idle = _server(n, src, dst, w), _server(n, src, dst, w)
  qids = [server.submit(QuerySpec("sssp", s)) for s in _sources(n, src)]
  with jax.profiler.trace(str(tmp_path)):
    assert server.step_round()
    assert not idle.step_round()
  retired = server.stats()["counters"]["slots.retired"]
  assert retired >= 1                  # the source without out-arcs

  spans = _host_spans(tmp_path)
  names = [s[0] for s in spans]
  rounds = [s for s in spans if s[0] == M.SPAN_ROUND]
  assert len(rounds) == 1              # an idle server records none
  _, lo, hi, _ = rounds[0]
  inside = [s for s in spans if lo <= s[1] and s[2] <= hi]
  assert len(inside) == len(spans)
  for child in (M.SPAN_ADMIT, M.SPAN_SUPERSTEPS, M.SPAN_RETIRE):
    assert names.count(child) == 1
  installs = [s for s in spans if s[0] == M.SPAN_INSTALL]
  assert sorted(s[3]["qid"] for s in installs) == qids
  assert names.count(M.SPAN_EXTRACT) == retired
  assert names.count(M.SPAN_SYNC) == 3 + retired
  (admit,) = [s for s in spans if s[0] == M.SPAN_ADMIT]
  (retire,) = [s for s in spans if s[0] == M.SPAN_RETIRE]
  assert all(admit[1] <= s[1] and s[2] <= admit[2] for s in installs)
  assert all(retire[1] <= s[1] and s[2] <= retire[2]
             for s in spans if s[0] == M.SPAN_EXTRACT)


def _serve(rmat_small, profile_dir=None):
  n, src, dst, w = rmat_small
  server = _server(n, src, dst, w)
  sources = _sources(n, src) + [5, 17, 40]
  qids = {server.submit(QuerySpec("sssp", s)): s for s in sources}
  if profile_dir is None:
    results = server.drain()
  else:
    with jax.profiler.trace(profile_dir):
      results = server.drain()
  snap = server.stats()
  snap["histograms"] = {k: v for k, v in snap["histograms"].items()
                        if "_ms" not in k}    # clock readings
  return {qids[q]: v for q, v in results.items()}, snap


def test_profiler_changes_no_answer_or_counter(rmat_small, tmp_path):
  plain, plain_stats = _serve(rmat_small)
  traced, traced_stats = _serve(rmat_small, str(tmp_path))
  assert plain.keys() == traced.keys()
  for s, v in plain.items():
    np.testing.assert_array_equal(v, traced[s])
  assert plain_stats == traced_stats
  assert not any(h.startswith("superstep.")
                 for h in plain_stats["histograms"])
  assert plain_stats["counters"]["rounds"] > 0
  assert plain_stats["counters"]["supersteps"] > 0
