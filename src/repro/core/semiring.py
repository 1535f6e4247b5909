"""Semirings for generalized sparse matrix operations.

GraphMat (Sundaram et al., 2015) maps vertex programs onto a *generalized*
SpMV in which the semiring multiply is replaced by the user's
``PROCESS_MESSAGE`` and the semiring add by the user's ``REDUCE``.  This
module provides the algebraic core: the table of built-in reduce kinds
(:data:`MONOIDS`: identity, combine, axis reduce, scatter and mesh
collective of each, and whether the Pallas kernel takes it), which every
backend reads, and a :class:`Semiring` value object with the standard
instances used by the paper's five algorithms.

The ``reduce`` operation must be associative and commutative (the paper makes
the same requirement) — this is what lets the backend parallelize the
reduction over edge blocks, vector lanes and mesh devices.  The one
exception is ``mode`` (the most frequent message), which is no monoid: it
has no binary combine and no identity, and only the COO backend runs it,
by a segmented sort of the arriving messages.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp

Array = jax.Array


def _extreme(dtype, top: bool) -> Array:
  """The dtype's largest (``top``) or smallest value; ±inf for floats."""
  if jnp.issubdtype(dtype, jnp.floating):
    return jnp.array(jnp.inf if top else -jnp.inf, dtype)
  info = jnp.iinfo(dtype)
  return jnp.array(info.max if top else info.min, dtype)


def _through_int8(collective):
  """A mesh collective of bool leaves, taken in int8."""
  return lambda x, axis_name: collective(
      x.astype(jnp.int8), axis_name=axis_name).astype(x.dtype)


@dataclasses.dataclass(frozen=True)
class Monoid:
  """One built-in reduce kind, as every path that runs it needs it.

  Attributes:
    identity: ``dtype -> scalar`` identity (``bool`` for any/all).
    combine: the binary combine of two arrays.
    reduce: the reduce over an axis, called as ``reduce(x, axis=...)``.
    scatter: the ``.at[]`` method that combines values into an array.
    collective: ``(x, axis_name) -> x``, the reduce across a mesh axis.
    pallas: whether the Pallas ELL kernel takes the kind.
  """

  identity: Callable[[Any], Array]
  combine: Callable[[Array, Array], Array]
  reduce: Callable[..., Array]
  scatter: str
  collective: Callable[[Array, str], Array]
  pallas: bool


# The reduce kinds that are monoids with hardware fast paths.  ``generic``
# (a user monoid, ``GraphProgram.reduce``) takes a segmented associative
# scan instead, and ``mode`` is no monoid (see the module docstring).
MONOIDS = {
    "add": Monoid(lambda dtype: jnp.zeros((), dtype), jnp.add, jnp.sum,
                  "add", jax.lax.psum, pallas=True),
    "min": Monoid(partial(_extreme, top=True), jnp.minimum, jnp.min, "min",
                  jax.lax.pmin, pallas=True),
    "max": Monoid(partial(_extreme, top=False), jnp.maximum, jnp.max, "max",
                  jax.lax.pmax, pallas=True),
    "any": Monoid(lambda dtype: jnp.zeros((), jnp.bool_), jnp.logical_or,
                  jnp.any, "max", _through_int8(jax.lax.pmax), pallas=False),
    "all": Monoid(lambda dtype: jnp.ones((), jnp.bool_), jnp.logical_and,
                  jnp.all, "min", _through_int8(jax.lax.pmin), pallas=False),
}
REDUCE_KINDS = (*MONOIDS, "generic", "mode")


def monoid(kind: str) -> Monoid:
  """The table entry of a built-in monoid kind."""
  try:
    return MONOIDS[kind]
  except KeyError:
    raise ValueError(f"reduce kind {kind!r} is no built-in monoid: it has "
                     "no default identity or combine") from None


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
  reduce_kind: str  # a key of MONOIDS

  def identity(self, dtype) -> Array:
    return monoid(self.reduce_kind).identity(dtype)

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
