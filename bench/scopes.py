"""Read a profile by the program's own names: the device time of each
``graphmat/`` named scope, and the host's ``graphmat.*`` spans with the idle
gaps of the device under them.

A profile records the HLO of each program it ran when
:func:`profiler_options` asks for it.  Each ``XLA Ops`` event of a device
plane is then put down to the module the ``XLA Modules`` line shows running
at its start, and to its instruction's ``op_name`` metadata in that module's
HLO; the outermost ``graphmat/`` scope there is its phase (``spmv/gather``),
and an operation with none is ``unscoped``.  Without HLO in the profile, the
device readings are empty.

``jax.profiler.ProfileData`` gives the events; the HLO sits in the
``/host:metadata`` plane's event metadata, which it does not expose, so that
part is read from the protobuf wire format (``XSpace`` → ``XPlane`` →
``XEventMetadata`` → ``HloProto`` → instructions →
``OpMetadata.op_name``).
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import re
from typing import Dict, Iterable, Iterator, List, Sequence, Tuple

from bench import trace as tr

MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
SPAN_PREFIX = "graphmat."
# A served round and its three phases, and a device-to-host fetch
# (``repro.service.metrics``).
ROUND, ADMIT, SUPERSTEPS, RETIRE = ROUND_SPANS = (
    "graphmat.round", "graphmat.round.admit", "graphmat.round.supersteps",
    "graphmat.round.retire")
SYNC = "graphmat.sync"
UNSCOPED = "unscoped"
OUTSIDE = "outside"
_PHASE = re.compile(r"graphmat/(send|apply|install|extract|spmv/[\w-]+)")

Interval = Tuple[float, float]


def profiler_options():
  """The benchmark's profiler options, with each program's HLO recorded."""
  import jax
  opts = jax.profiler.ProfileOptions()
  opts.python_tracer_level = 0
  opts.enable_hlo_proto = True
  return opts


def phase_of(op_name: str) -> str:
  """The outermost ``graphmat/`` phase in an ``op_name``, or ``unscoped``."""
  m = _PHASE.search(op_name)
  return m.group(1) if m else UNSCOPED


# ---------------------------------------------------------------------------
# The HLO in the profile (protobuf wire format)
# ---------------------------------------------------------------------------


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
  out = shift = 0
  while True:
    b = buf[i]
    i += 1
    out |= (b & 0x7F) << shift
    shift += 7
    if b < 0x80:
      return out, i


def _fields(buf: bytes) -> Iterator[Tuple[int, object]]:
  """(field number, value) of one message: ints, or bytes for lengths."""
  i, n = 0, len(buf)
  while i < n:
    key, i = _varint(buf, i)
    kind = key & 7
    if kind == 0:
      value, i = _varint(buf, i)
    elif kind == 2:
      size, i = _varint(buf, i)
      value, i = buf[i:i + size], i + size
    elif kind == 1:
      value, i = buf[i:i + 8], i + 8
    elif kind == 5:
      value, i = buf[i:i + 4], i + 4
    else:
      raise ValueError(f"wire type {kind} in a profile")
    yield key >> 3, value


def _first(buf: bytes, field: int, default=b""):
  for f, v in _fields(buf):
    if f == field:
      return v
  return default


def _op_names(hlo_proto: bytes) -> Dict[str, str]:
  """Instruction name -> ``op_name`` of every computation of a module
  (``HloProto.hlo_module.computations[].instructions[]``)."""
  out = {}
  for f, comp in _fields(_first(hlo_proto, 1)):
    if f != 3:
      continue
    for g, instr in _fields(comp):
      if g == 2:
        name = _first(instr, 1).decode()
        out[name] = _first(_first(instr, 7), 2).decode()
  return out


def read_hlo(path: str) -> Dict[str, Dict[str, str]]:
  """Module (as the ``XLA Modules`` line names it, ``jit_f(7)``) ->
  instruction -> ``op_name``, from the HLO a profile recorded."""
  with open(path, "rb") as f:
    space = f.read()
  out = {}
  for f, plane in _fields(space):
    if f != 1 or _first(plane, 2) != METADATA_PLANE.encode():
      continue
    stat_names = {}
    for g, entry in _fields(plane):
      if g == 5:
        meta = _first(entry, 2)
        stat_names[_first(entry, 1, 0)] = _first(meta, 2).decode()
    for g, entry in _fields(plane):
      if g != 4:
        continue
      meta = _first(entry, 2)
      for h, stat in _fields(meta):
        if h == 5 and stat_names.get(_first(stat, 1, 0)) == HLO_STAT:
          out[_first(meta, 2).decode()] = _op_names(_first(stat, 6))
  return out


# ---------------------------------------------------------------------------
# Device operations by phase
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Op:
  name: str          # the HLO instruction (``fusion.67``)
  module: str        # the module running it (``jit__pagerank_jit(12)``)
  phase: str
  start_ns: float
  end_ns: float


def read_ops(path: str) -> Dict[str, List[Op]]:
  """Device plane -> its operations with their phase; empty where the
  profile holds no HLO."""
  from jax.profiler import ProfileData
  hlo = read_hlo(path)
  if not hlo:
    return {}
  out: Dict[str, List[Op]] = {}
  for plane in ProfileData.from_file(path).planes:
    if not plane.name.startswith(tr.DEVICE_PREFIX):
      continue
    lines = {line.name: line for line in plane.lines}
    if tr.OPS_LINE not in lines:
      continue
    mods = sorted((e.start_ns, e.name) for e in lines[MODULES_LINE].events
                  ) if MODULES_LINE in lines else []
    starts = [s for s, _ in mods]
    ops = out.setdefault(plane.name, [])
    for e in lines[tr.OPS_LINE].events:
      i = bisect.bisect_right(starts, e.start_ns) - 1
      module = mods[i][1] if i >= 0 else ""
      name = tr.op_name(e.name)
      phase = phase_of(hlo.get(module, {}).get(name, ""))
      ops.append(Op(name, module, phase, e.start_ns, e.end_ns))
  return out


def split_busy(intervals: Sequence[Tuple[float, float, str]], lo: float,
               hi: float, under: str = None) -> Dict[str, float]:
  """Busy time (ns) of ``[lo, hi)`` by label: where labelled intervals
  overlap, the time is split evenly among them, so the labels' times add
  up to the union of the intervals.  Intervals labelled ``under`` count
  only where no other runs (control flow around the operations it runs)."""
  points = []
  for s, e, label in intervals:
    s, e = max(s, lo), min(e, hi)
    if e > s:
      points += [(s, 1, label), (e, -1, label)]
  points.sort(key=lambda p: (p[0], p[1]))
  out: Dict[str, float] = collections.defaultdict(float)
  live: Dict[str, int] = collections.Counter()
  last = None
  for t, step, label in points:
    if last is not None and t > last:
      top = {k: n for k, n in live.items() if n and k != under}
      if not top and live[under]:
        top = {under: 1}
      depth = sum(top.values())
      for name, n in top.items():
        out[name] += (t - last) * n / depth
    live[label] += step
    last = t
  return dict(out)


def phase_seconds(ops: Dict[str, List[Op]], lo: float, hi: float
                  ) -> Tuple[Dict[str, float], Dict[str, int]]:
  """Device seconds and operation count per phase inside ``[lo, hi)``,
  averaged over the device planes.  Control flow (a ``while`` spans the
  operations of its loop) counts as ``unscoped`` only where no operation
  runs, so the seconds add up to the busy time of
  :func:`bench.trace.summarize`; it is not counted as an operation."""
  secs: Dict[str, float] = collections.defaultdict(float)
  count: Dict[str, int] = collections.Counter()
  control = "(control)"
  for plane_ops in ops.values():
    labelled = [(o.start_ns, o.end_ns,
                 control if o.name.startswith(tr.CONTROL_OPS) else o.phase)
                for o in plane_ops]
    for label, ns in split_busy(labelled, lo, hi, under=control).items():
      secs[UNSCOPED if label == control else label] += ns * 1e-9 / len(ops)
    for s, e, label in labelled:
      if label != control and e > lo and s < hi:
        count[label] += 1
  return dict(secs), dict(count)


# ---------------------------------------------------------------------------
# Host spans, and the device's idle time under them
# ---------------------------------------------------------------------------


def span_seconds(host: Iterable[tr.Event], lo: float, hi: float
                 ) -> Tuple[Dict[str, float], Dict[str, int]]:
  """Seconds (clipped to ``[lo, hi)``) and count (those that start in it)
  of the program's host spans (``graphmat.*``)."""
  secs: Dict[str, float] = collections.defaultdict(float)
  count: Dict[str, int] = collections.Counter()
  for e in host:
    if not e.name.startswith(SPAN_PREFIX):
      continue
    s, t = max(e.start_ns, lo), min(e.end_ns, hi)
    if t > s:
      secs[e.name] += (t - s) * 1e-9
    if lo <= e.start_ns < hi:
      count[e.name] += 1
  return dict(secs), dict(count)


def innermost(spans: Iterable[tr.Event]) -> List[Tuple[float, float, str]]:
  """Disjoint ``(start, end, name)`` pieces of time, each named after the
  innermost span over it: the one that started last (the shortest on a
  tie)."""
  points = []
  for k, e in enumerate(spans):
    if e.end_ns > e.start_ns:
      points += [(e.start_ns, 1, k, e), (e.end_ns, -1, k, e)]
  points.sort(key=lambda p: (p[0], p[1]))
  live: Dict[int, tr.Event] = {}
  out: List[Tuple[float, float, str]] = []
  last = None
  for t, step, k, e in points:
    if live and t > last:
      top = max(live.values(), key=lambda x: (x.start_ns, -x.end_ns))
      if out and out[-1][2] == top.name and out[-1][1] == last:
        out[-1] = (out[-1][0], t, top.name)
      else:
        out.append((last, t, top.name))
    if step > 0:
      live[k] = e
    else:
      live.pop(k, None)
    last = t
  return out


def idle_under(idle: Sequence[Interval], spans: Iterable[tr.Event]
               ) -> Dict[str, float]:
  """Seconds of every idle gap, put down to the innermost of ``spans``
  over each part of it, or to ``outside``."""
  pieces = innermost(spans)
  out: Dict[str, float] = collections.defaultdict(float)
  j = 0
  for s, e in sorted(idle):
    covered = 0.0
    while j < len(pieces) and pieces[j][1] <= s:
      j += 1
    k = j
    while k < len(pieces) and pieces[k][0] < e:
      ps, pe, name = pieces[k]
      part = min(pe, e) - max(ps, s)
      if part > 0:
        out[name] += part * 1e-9
        covered += part
      k += 1
    if e - s > covered:
      out[OUTSIDE] += (e - s - covered) * 1e-9
  return dict(out)


# ---------------------------------------------------------------------------
# One reading of a profile
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Reading:
  window_s: float
  busy_s: float
  phase_s: Dict[str, float]       # device seconds per phase (+ unscoped)
  phase_n: Dict[str, int]         # device operations per phase
  span_s: Dict[str, float]        # host seconds per graphmat.* span
  span_n: Dict[str, int]          # spans that start in the window
  idle_s: Dict[str, float]        # idle seconds by innermost span / outside
  idle_round_s: Dict[str, float]  # the same by ROUND_SPANS alone
  top_ops: List[list]             # [op, module, phase, seconds], longest first


def read(path: str, window: Interval = None) -> Reading:
  """All the readings of one profile inside ``window`` (the
  :data:`bench.trace.WINDOW_SPAN` span when None)."""
  trace = tr.read_xplane(path)
  lo, hi = window if window is not None else tr.window_of(trace)
  summary = tr.summarize(trace, (lo, hi))
  ops = read_ops(path)
  phase_s, phase_n = phase_seconds(ops, lo, hi)
  span_s, span_n = span_seconds(trace.host, lo, hi)
  first = sorted(trace.device)[0]
  idle = tr.gaps(tr.merge(tr.clip(((e.start_ns, e.end_ns)
                                   for e in trace.device[first]), lo, hi)),
                 lo, hi)
  spans = [e for e in trace.host if e.name.startswith(SPAN_PREFIX)]
  by_op: Dict[Tuple[str, str, str], float] = collections.defaultdict(float)
  for plane_ops in ops.values():
    for o in plane_ops:
      s, e = max(o.start_ns, lo), min(o.end_ns, hi)
      if e > s and not o.name.startswith(tr.CONTROL_OPS):
        by_op[(o.name, o.module, o.phase)] += (e - s) * 1e-9 / len(ops)
  top = [[*k, v] for k, v in sorted(by_op.items(), key=lambda kv: -kv[1])]
  return Reading(
      summary.window_s, summary.busy_s, phase_s, phase_n, span_s, span_n,
      idle_under(idle, spans),
      idle_under(idle, [e for e in spans if e.name in ROUND_SPANS]),
      top[:12])


def numbers(r: Reading) -> dict:
  """What the per-layer metrics proposed for these scopes would report:
  ``phase_share`` (percent of busy time per phase), ``host_round_ms``
  (idle milliseconds under admission and retirement per round),
  ``syncs_per_round`` and ``round_idle_share`` (percent of the idle time
  under a round); the round's numbers only where rounds ran."""
  out = {"phase_share": {k: 100.0 * v / r.busy_s
                         for k, v in sorted(r.phase_s.items())}
         if r.busy_s > 0 else {}}
  rounds = r.span_n.get(ROUND, 0)
  idle = sum(r.idle_round_s.values())
  if rounds:
    host = r.idle_round_s.get(ADMIT, 0.0) + r.idle_round_s.get(RETIRE, 0.0)
    out["host_round_ms"] = 1e3 * host / rounds
    out["syncs_per_round"] = r.span_n.get(SYNC, 0) / rounds
    if idle > 0:
      out["round_idle_share"] = 100.0 * (idle - r.idle_round_s.get(
          OUTSIDE, 0.0)) / idle
  return out
