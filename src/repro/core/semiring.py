"""Semirings for generalized sparse matrix operations.

GraphMat (Sundaram et al., 2015) maps vertex programs onto a *generalized*
SpMV in which the semiring multiply is replaced by the user's
``PROCESS_MESSAGE`` and the semiring add by the user's ``REDUCE``.  This
module provides the algebraic core: a :class:`Semiring` value object plus the
standard instances used by the paper's five algorithms.

The ``reduce`` operation must be associative and commutative (the paper makes
the same requirement) — this is what lets the backend parallelize the
reduction over edge blocks, vector lanes and mesh devices.  The one
exception is ``mode`` (the most frequent message), which is no monoid: it
has no binary combine and no identity, and only the COO backend runs it,
by a segmented sort of the arriving messages.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

Array = jax.Array

# Reduction kinds with hardware fast-paths.  ``generic`` falls back to a
# segmented associative scan (still parallel, but no scatter fast-path).
# ``mode`` is not a monoid (see the module docstring).
REDUCE_KINDS = ("add", "min", "max", "any", "all", "generic", "mode")


def _identity_for(kind: str, dtype) -> Any:
  if kind == "add":
    return jnp.zeros((), dtype)
  if kind == "min":
    if jnp.issubdtype(dtype, jnp.floating):
      return jnp.array(jnp.inf, dtype)
    return jnp.array(jnp.iinfo(dtype).max, dtype)
  if kind == "max":
    if jnp.issubdtype(dtype, jnp.floating):
      return jnp.array(-jnp.inf, dtype)
    return jnp.array(jnp.iinfo(dtype).min, dtype)
  if kind == "any":
    return jnp.zeros((), jnp.bool_)
  if kind == "all":
    return jnp.ones((), jnp.bool_)
  raise ValueError(f"no default identity for reduce kind {kind!r}")


def reduce_fn_for(kind: str) -> Callable[[Array, Array], Array]:
  return {
      "add": jnp.add,
      "min": jnp.minimum,
      "max": jnp.maximum,
      "any": jnp.logical_or,
      "all": jnp.logical_and,
  }[kind]


@dataclasses.dataclass(frozen=True)
class Semiring:
  """A (add, mul) pair with identities, in GraphMat's generalized sense.

  ``mul(x_src, edge)`` plays PROCESS_MESSAGE restricted to (message, edge)
  — the classical CombBLAS-style semiring.  GraphMat's extension (reading the
  destination vertex property) lives one level up, in
  :class:`repro.core.vertex_program.GraphProgram`.
  """

  name: str
  add: Callable[[Array, Array], Array]
  mul: Callable[[Array, Array], Array]
  reduce_kind: str  # one of REDUCE_KINDS; used to pick scatter fast-paths.

  def identity(self, dtype) -> Array:
    return _identity_for(self.reduce_kind, dtype)

  def __repr__(self) -> str:  # pragma: no cover - cosmetic
    return f"Semiring({self.name})"


# The classical instances.  Names follow GraphBLAS conventions.
PLUS_TIMES = Semiring("plus_times", jnp.add, jnp.multiply, "add")
MIN_PLUS = Semiring("min_plus", jnp.minimum, jnp.add, "min")
MAX_TIMES = Semiring("max_times", jnp.maximum, jnp.multiply, "max")
OR_AND = Semiring("or_and", jnp.logical_or, jnp.logical_and, "any")
# BFS: the message *is* the value, the edge is ignored; REDUCE = min.
MIN_FIRST = Semiring("min_first", jnp.minimum, lambda m, e: m, "min")


def popcount(x: Array) -> Array:
  """Per-lane population count for packed bitmap payloads (triangle counting)."""
  return jax.lax.population_count(x)


def tree_select(mask: Array, a, b):
  """``jnp.where`` over pytrees, broadcasting ``mask`` over trailing dims."""

  def sel(x, y):
    m = mask.reshape(mask.shape + (1,) * (x.ndim - mask.ndim))
    return jnp.where(m, x, y)

  return jax.tree_util.tree_map(sel, a, b)


def tree_full_like(tree, fill):
  """A pytree of ``full_like`` arrays; ``fill`` may be a pytree of scalars."""
  if isinstance(fill, (int, float, bool)) or (
      hasattr(fill, "ndim") and getattr(fill, "ndim", None) == 0
  ):
    return jax.tree_util.tree_map(lambda x: jnp.full_like(x, fill), tree)
  return jax.tree_util.tree_map(
      lambda x, f: jnp.full_like(x, f), tree, fill
  )
