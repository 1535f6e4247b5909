"""Jitted wrappers bridging :mod:`repro.core` to the Pallas kernels."""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp

from repro.core import graph as graphlib
from repro.core.backends.pallas import kernel_takes
from repro.core.spmv import SCOPE_GATHER, _unpermute, fold_split
from repro.core.vertex_program import GraphProgram
from repro.kernels.ell_spmv import ell_spmv_pallas

Array = jax.Array
PyTree = Any


def spmv_ell_pallas(g: graphlib.EllGraph, msg: PyTree, active: Array,
                    dst_prop: PyTree, program: GraphProgram,
                    **kernel_kwargs) -> Tuple[PyTree, Array]:
  """Drop-in replacement for :func:`repro.core.spmv.spmv_ell` that routes the
  packed-ELL rows, split rows included, through the Pallas kernel.

  Takes the calls :func:`repro.core.backends.pallas.kernel_takes` accepts.
  """
  if not kernel_takes(program, msg, dst_prop):
    raise ValueError(
        f"program {program.name!r}: the Pallas kernel takes a reduce kind "
        "whose MONOIDS entry says pallas, one message leaf ([n], or the "
        "[n, Q] lanes of a lanewise program) and at most one destination-"
        f"property leaf; got reduce_kind={program.reduce_kind!r}")
  msg_leaves, msg_def = jax.tree_util.tree_flatten(msg)
  m = msg_leaves[0]

  dpp = None
  if program.process_reads_dst:
    dp_leaf, = jax.tree_util.tree_leaves(dst_prop)
    with jax.named_scope(SCOPE_GATHER):
      dpp = dp_leaf[jnp.minimum(g.row_of, g.n - 1)]

  y_leaf, recv_packed = ell_spmv_pallas(
      g.cols, g.vals, g.mask, m, active, dpp,
      process=program.process_message, reduce_kind=program.reduce_kind,
      slot_rows=g.slot_rows, **kernel_kwargs)
  y_packed = jax.tree_util.tree_unflatten(msg_def, [y_leaf])

  y, recv = _unpermute(g, y_packed, recv_packed)
  return fold_split(g, y, recv, y_packed, recv_packed, program)
