"""The GraphMat vertex-program API.

Mirrors the paper's user-facing surface (Section 4.1 and the SSSP appendix):

* ``send_message(vertex_property) -> message`` — run for each *active* vertex.
* ``process_message(message, edge_value, dst_property) -> result`` — run per
  edge.  Reading the destination vertex property is GraphMat's key
  expressivity extension over CombBLAS/PEGASUS (enables TC and CF).
* ``reduce(a, b) -> a⊕b`` — associative + commutative combine of processed
  messages arriving at one vertex (or ``mode``: the most frequent one, which
  is no such combine).
* ``apply(reduced, old_property) -> new_property`` — run for each vertex that
  received at least one message.
* a vertex whose property *changed* under ``apply`` becomes active for the
  next superstep (the paper's default activation rule; overridable).

Properties and messages may be arbitrary pytrees of arrays with a leading
vertex axis — CF uses K-vector latent factors, TC uses packed ``uint32``
bitmap rows.  All callables must be JAX-traceable; they are inlined into the
backend SpMV at trace time (the TPU analogue of the paper's ``-ipo``
inter-procedural-optimization requirement — we get that fusion for free).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import semiring as sr

Array = jax.Array
PyTree = Any


def _default_activate(old: PyTree, new: PyTree) -> Array:
  """Active iff any leaf differs (per vertex, reducing over trailing dims)."""
  leaves_old = jax.tree_util.tree_leaves(old)
  leaves_new = jax.tree_util.tree_leaves(new)
  per_leaf = []
  for o, n in zip(leaves_old, leaves_new):
    d = o != n
    if d.ndim > 1:  # reduce trailing payload dims, keep the vertex axis
      d = jnp.any(d.reshape(d.shape[0], -1), axis=-1)
    per_leaf.append(d)
  out = per_leaf[0]
  for d in per_leaf[1:]:
    out = jnp.logical_or(out, d)
  return out


def lanewise_activate(old: PyTree, new: PyTree) -> Array:
  """Per-lane activation for batched (multi-query) programs.

  Property leaves carry a query axis at dim 1 (``[n, Q, ...]``); the frontier
  is ``bool[n, Q]``.  A lane re-activates iff any of *its* payload changed —
  trailing dims beyond the query axis are reduced, the query axis is kept.
  """
  leaves_old = jax.tree_util.tree_leaves(old)
  leaves_new = jax.tree_util.tree_leaves(new)
  per_leaf = []
  for o, n in zip(leaves_old, leaves_new):
    d = o != n
    if d.ndim > 2:  # reduce payload dims beyond [n, Q]
      d = jnp.any(d.reshape(d.shape[0], d.shape[1], -1), axis=-1)
    per_leaf.append(d)
  out = per_leaf[0]
  for d in per_leaf[1:]:
    out = jnp.logical_or(out, d)
  return out


@dataclasses.dataclass(frozen=True)
class GraphProgram:
  """A GraphMat vertex program (see module docstring).

  Attributes:
    process_message: ``(message, edge_value, dst_property) -> result``.
    reduce_kind: one of :data:`repro.core.semiring.REDUCE_KINDS`.  Fast
      scatter paths exist for add/min/max/any/all; ``generic`` uses a
      segmented associative scan and requires ``reduce``.  ``mode`` is not
      associative: the result is the value that arrives most often, ties
      to the smallest.  It takes integer scalar messages only, has no
      ``reduce`` and no identity, and runs on the COO backend alone.
    reduce: explicit combine fn (required for ``generic``; derived otherwise).
    reduce_identity: pytree of scalar identities matching the *result*
      structure (required for ``generic``; derived otherwise).
    send_message: ``(vertex_property) -> message`` (vectorized via vmap).
      Defaults to the identity (message = property), the most common case.
    apply: ``(reduced, old_property) -> new_property``.
    activate: ``(old_property, new_property) -> bool[n]``-leaf rule deciding
      the next frontier.  Defaults to "property changed" as in the paper.
    process_reads_dst: set False when ``process_message`` ignores the
      destination property — lets backends skip materializing the gather.
    needs_recv: set False for *monotone* programs (APPLY(identity, old) ==
      old, e.g. min/max relaxations): the backend skips the receive-mask
      scatter and the engine applies unconditionally — one fewer E-sized
      pass per superstep (a paper-§4.5-style backend optimization).
    num_message_dims: trailing dims of the message payload (0 = scalar,
      1 = vector messages as in CF/TC).
    inert_message: optional pytree of *scalars* (matching the message
      structure) that annihilates a lane: the program must guarantee
      ``APPLY(REDUCE(y, PROCESS(inert, e, d)), old) == APPLY(y, old)`` — i.e.
      an edge whose source sends the inert message cannot change any
      destination.  Required for batched (multi-query) execution, where
      per-query frontier masking is folded into the message payload
      (inactive lanes send ``inert_message``).  Examples: +∞ for min-plus
      relaxations (BFS/SSSP), 0.0 for add-reduce rank flows (PageRank).
    lanewise: declare that process/reduce/apply act independently on each
      trailing payload lane (no cross-lane mixing, unlike CF's K-factor dot
      products).  Lets backends tile the payload/query axis — in particular
      the Pallas kernel's multi-query column tiles.
  """

  process_message: Callable[[PyTree, Array, PyTree], PyTree]
  reduce_kind: str = "add"
  reduce: Optional[Callable[[PyTree, PyTree], PyTree]] = None
  reduce_identity: Optional[PyTree] = None
  send_message: Callable[[PyTree], PyTree] = lambda p: p
  apply: Callable[[PyTree, PyTree], PyTree] = lambda red, old: red
  activate: Callable[[PyTree, PyTree], Array] = _default_activate
  process_reads_dst: bool = True
  needs_recv: bool = True
  num_message_dims: int = 0
  inert_message: Optional[PyTree] = None
  lanewise: bool = False
  name: str = "graph_program"

  def __post_init__(self):
    if self.reduce_kind not in sr.REDUCE_KINDS:
      raise ValueError(
          f"reduce_kind={self.reduce_kind!r} not in {sr.REDUCE_KINDS}")
    if self.reduce_kind == "generic" and self.reduce is None:
      raise ValueError("generic reduce_kind requires an explicit `reduce`")
    if self.reduce_kind == "mode" and (
        self.reduce is not None or self.reduce_identity is not None
        or self.num_message_dims != 0):
      raise ValueError("mode reduce_kind takes scalar messages and no "
                       "`reduce` or `reduce_identity`")

  # -- derived helpers -------------------------------------------------------

  def reduce_fn(self) -> Callable[[PyTree, PyTree], PyTree]:
    if self.reduce_kind == "mode":
      raise ValueError(f"program {self.name!r}: mode has no binary reduce")
    if self.reduce is not None:
      return self.reduce
    leaf = sr.reduce_fn_for(self.reduce_kind)
    return lambda a, b: jax.tree_util.tree_map(leaf, a, b)

  def identity_like(self, result_tree: PyTree) -> PyTree:
    """Pytree of identity scalars shaped like ``result_tree`` leaves."""
    if self.reduce_identity is not None:
      return jax.tree_util.tree_map(
          lambda x, i: jnp.full_like(x, i), result_tree, self.reduce_identity)
    return jax.tree_util.tree_map(
        lambda x: jnp.full_like(x, sr._identity_for(self.reduce_kind, x.dtype)),
        result_tree)

  def from_semiring(self):  # pragma: no cover - convenience alias
    raise NotImplementedError


def program_from_semiring(s: sr.Semiring, name: str = "") -> GraphProgram:
  """Lift a classical semiring into the vertex-program API."""
  return GraphProgram(
      process_message=lambda m, e, d: s.mul(m, e),
      reduce_kind=s.reduce_kind,
      process_reads_dst=False,
      name=name or f"semiring:{s.name}",
  )
