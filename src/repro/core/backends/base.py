"""Backend protocol + registry — the single extension point for SpMV execution.

A backend is one strategy for the generalized SpMV ``y[v] = ⊕ process(msg[u],
w_uv, prop[v])``.  The built-ins (dense / coo / coo_tiled / ell / pallas)
register themselves when :mod:`repro.core.backends` is imported; registering
a new backend makes it reachable from every engine entry point, the service
layer, and the cross-backend conformance suite with no dispatcher edits —
the if/elif chain the registry replaced.

Resolution semantics (:func:`resolve`) preserve the legacy string-kwarg
behavior: the graph *container* dominates.  An explicit plan naming a backend
that cannot execute the call (e.g. ``Plan(backend="ell")`` on a
:class:`~repro.core.graph.CooGraph`) falls back to structural auto-selection,
exactly as ``backend="ell"`` used to fall through the old isinstance chain.
A ``mode`` program never falls back: its reduce exists on one backend only,
so an explicit plan that cannot run it is an error.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax

from repro.core.backends.plan import Plan
from repro.core.vertex_program import GraphProgram

Array = jax.Array
PyTree = Any


class Backend:
  """One generalized-SpMV execution strategy.

  Class attributes:
    name: registry key (also the legacy string spelling).
    container: preferred graph container — ``"dense" | "coo" | "ell"``, or
      ``"dist"`` for a DistGraph on its mesh (``distributed.Mesh2dBackend``).
      Drives test-harness graph construction (a new backend declares which
      container to build and inherits the conformance suite for free).
    priority: structural-auto tie-break; higher is tried first.
  """

  name: str = "?"
  container: str = "coo"
  priority: int = 0

  def supports(self, graph, msg: PyTree, dst_prop: PyTree,
               program: GraphProgram) -> bool:
    """Hard capability: can this backend execute this call at all?"""
    raise NotImplementedError

  def eligible(self, graph, msg: PyTree, dst_prop: PyTree,
               program: GraphProgram) -> bool:
    """Should structural auto-selection pick this backend?  Defaults to
    :meth:`supports`; override to opt out of auto (e.g. planner-only
    backends) or to add profitability conditions."""
    return self.supports(graph, msg, dst_prop, program)

  def execute(self, graph, msg: PyTree, active: Array, dst_prop: PyTree,
              program: GraphProgram, plan: Plan, with_recv: bool
              ) -> Tuple[PyTree, Optional[Array]]:
    """Run the generalized SpMV.  ``plan`` carries this backend's tile
    parameters; unknown fields are ignored."""
    raise NotImplementedError

  def __repr__(self) -> str:
    return f"<{type(self).__name__} {self.name!r}>"


_REGISTRY: Dict[str, Backend] = {}


def register(backend: Backend, *, replace: bool = False) -> Backend:
  """Add a backend to the registry (the extension point)."""
  if not backend.name or backend.name == "auto":
    raise ValueError(f"invalid backend name {backend.name!r}")
  if backend.name in _REGISTRY and not replace:
    raise ValueError(
        f"backend {backend.name!r} already registered (pass replace=True)")
  _REGISTRY[backend.name] = backend
  return backend


def unregister(name: str) -> None:
  _REGISTRY.pop(name, None)


def get_backend(name: str) -> Backend:
  try:
    return _REGISTRY[name]
  except KeyError:
    raise KeyError(
        f"no backend {name!r} registered; have {registered_backends()}"
        ) from None


def registered_backends() -> Tuple[str, ...]:
  """Registered backend names, highest structural priority first."""
  return tuple(sorted(_REGISTRY, key=lambda k: -_REGISTRY[k].priority))


def resolve(plan: Plan, graph, msg: PyTree, dst_prop: PyTree,
            program: GraphProgram) -> Backend:
  """Pick the backend executing this call.

  An explicitly named backend wins iff it supports the call; otherwise the
  container dominates (legacy string semantics) and selection falls through
  to structural auto: highest-priority backend whose :meth:`Backend.eligible`
  accepts the (graph, payload, program) triple.  An explicit plan that
  cannot run a ``mode`` program raises instead.
  """
  if not plan.is_auto:
    impl = get_backend(plan.backend)
    if impl.supports(graph, msg, dst_prop, program):
      return impl
    if program.reduce_kind == "mode":
      raise TypeError(
          f"backend {plan.backend!r} cannot run the mode reduce of program "
          f"{program.name!r} on {type(graph).__name__}")
  for name in registered_backends():
    impl = _REGISTRY[name]
    if impl.eligible(graph, msg, dst_prop, program):
      return impl
  raise TypeError(
      f"no registered backend supports graph container {type(graph).__name__}"
      f" with program {program.name!r} (registered: {registered_backends()})")
