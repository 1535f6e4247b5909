"""Reduce a profiler trace (``.xplane.pb``) to device busy time, per-name
operation time and idle gaps.

Device operations are the events of the ``XLA Ops`` line of each
``/device:`` plane, named by their HLO instruction (``ell_spmv.10``).  Host spans are the events of every ``/host:`` plane
line; the benchmark's own spans (``jax.profiler.TraceAnnotation``) are among
them, and the span named :data:`WINDOW_SPAN` marks the traced window.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

DEVICE_PREFIX = "/device:"
HOST_PREFIX = "/host:"
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
CONTROL_OPS = ("while", "conditional", "call.")
MAX_ATTRIBUTED = 200     # idle gaps attributed one by one, longest first

Interval = Tuple[float, float]


@dataclasses.dataclass(frozen=True)
class Event:
  name: str
  start_ns: float
  end_ns: float


@dataclasses.dataclass
class Trace:
  device: Dict[str, List[Event]]     # device plane name -> its operations
  host: List[Event]                  # every host span


@dataclasses.dataclass
class Summary:
  window_s: float
  busy_s: float                      # union of device ops, mean over devices
  op_seconds: Dict[str, float]       # device op name -> summed duration
  idle_gaps: List[Tuple[str, float]]  # host span name -> idle seconds


def find_xplane(log_dir: str) -> str:
  """The one ``.xplane.pb`` the profiler wrote under ``log_dir``."""
  found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                    recursive=True)
  if len(found) != 1:
    raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                       f"found {found}")
  return found[0]


def op_name(event_name: str) -> str:
  """``fusion.55`` of ``%fusion.55 = f32[...] fusion(...)``."""
  return event_name.split(" = ", 1)[0].lstrip("%")


def read_xplane(path: str) -> Trace:
  from jax.profiler import ProfileData
  data = ProfileData.from_file(path)
  device: Dict[str, List[Event]] = {}
  host: List[Event] = []
  for plane in data.planes:
    if plane.name.startswith(DEVICE_PREFIX):
      # Planes without an ops line (``/device:CUSTOM:...``) run no program.
      for line in plane.lines:
        if line.name == OPS_LINE:
          device.setdefault(plane.name, []).extend(
              Event(op_name(e.name), e.start_ns, e.end_ns)
              for e in line.events)
    elif plane.name.startswith(HOST_PREFIX):
      for line in plane.lines:
        host.extend(Event(e.name, e.start_ns, e.end_ns) for e in line.events
                    if e.end_ns > e.start_ns)
  return Trace(device, host)


def merge(intervals: Iterable[Interval]) -> List[Interval]:
  """Union of ``[start, end)`` intervals as sorted, disjoint intervals."""
  out: List[List[float]] = []
  for s, e in sorted(intervals):
    if e <= s:
      continue
    if out and s <= out[-1][1]:
      out[-1][1] = max(out[-1][1], e)
    else:
      out.append([s, e])
  return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
  return [(max(s, lo), min(e, hi)) for s, e in intervals
          if e > lo and s < hi]


def covered(merged: Sequence[Interval]) -> float:
  return sum(e - s for s, e in merged)


def gaps(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
  """The parts of ``[lo, hi)`` that ``merged`` leaves uncovered."""
  out, t = [], lo
  for s, e in merged:
    if s > t:
      out.append((t, min(s, hi)))
    t = max(t, e)
    if t >= hi:
      break
  if t < hi:
    out.append((t, hi))
  return [(s, e) for s, e in out if e > s]


def sums_by_name(events: Iterable[Event], lo: float = float("-inf"),
                 hi: float = float("inf")) -> Dict[str, float]:
  """Summed duration (ns) per name of the events clipped to ``[lo, hi)``,
  leaving out control flow (a ``while`` spans the operations it runs)."""
  out: Dict[str, float] = collections.defaultdict(float)
  for ev in events:
    if ev.name.startswith(CONTROL_OPS):
      continue
    s, e = max(ev.start_ns, lo), min(ev.end_ns, hi)
    if e > s:
      out[ev.name] += e - s
  return dict(out)


def window_of(trace: Trace, span: str = WINDOW_SPAN) -> Interval:
  spans = [(e.start_ns, e.end_ns) for e in trace.host if e.name == span]
  if not spans:
    raise RuntimeError(f"no host span {span!r} in the trace")
  return min(s for s, _ in spans), max(e for _, e in spans)


class HostIndex:
  """Host spans as arrays, for attributing idle gaps to what the host did."""

  def __init__(self, host: Sequence[Event],
               skip: Sequence[str] = (WINDOW_SPAN,)):
    kept = [e for e in host if e.name not in skip]
    self.names = [e.name for e in kept]
    self.start = np.asarray([e.start_ns for e in kept], np.float64)
    self.end = np.asarray([e.end_ns for e in kept], np.float64)

  def attribute(self, gap: Interval) -> str:
    """The innermost host span that covers at least half as much of the gap
    as the span covering most of it."""
    if not self.names:
      return "none"
    ov = np.minimum(self.end, gap[1]) - np.maximum(self.start, gap[0])
    most = ov.max()
    if most <= 0:
      return "none"
    dur = np.where(ov >= 0.5 * most, self.end - self.start, np.inf)
    return self.names[int(np.argmin(dur))]


def attribute_gaps(idle: Sequence[Interval], index: HostIndex,
                   longest: int = MAX_ATTRIBUTED) -> Dict[str, float]:
  """Idle seconds by host span: the ``longest`` gaps one by one, the rest
  together under ``shorter gaps``."""
  ranked = sorted(idle, key=lambda g: g[0] - g[1])
  out: Dict[str, float] = collections.defaultdict(float)
  for g in ranked[:longest]:
    out[index.attribute(g)] += (g[1] - g[0]) * 1e-9
  rest = sum(e - s for s, e in ranked[longest:])
  if rest:
    out["shorter gaps"] += rest * 1e-9
  return dict(out)


def summarize(trace: Trace, window: Optional[Interval] = None) -> Summary:
  """Busy time, op sums and idle gaps of ``trace`` inside ``window`` (the
  :data:`WINDOW_SPAN` span when None)."""
  if not trace.device:
    raise RuntimeError("the trace has no device plane")
  lo, hi = window if window is not None else window_of(trace)
  busy, ops = [], collections.defaultdict(float)
  idle: Dict[str, float] = {}
  for i, plane in enumerate(sorted(trace.device)):
    events = trace.device[plane]
    merged = merge(clip(((e.start_ns, e.end_ns) for e in events), lo, hi))
    busy.append(covered(merged))
    for name, ns in sums_by_name(events, lo, hi).items():
      ops[name] += ns
    if i == 0:
      idle = attribute_gaps(gaps(merged, lo, hi), HostIndex(trace.host))
  return Summary(
      window_s=(hi - lo) * 1e-9,
      busy_s=sum(busy) / len(busy) * 1e-9,
      op_seconds={k: v * 1e-9 for k, v in ops.items()},
      idle_gaps=sorted(idle.items(), key=lambda kv: -kv[1]))


def top(items: Dict[str, float], k: int = 10) -> List[list]:
  return [[name, sec] for name, sec in
          sorted(items.items(), key=lambda kv: -kv[1])[:k]]
