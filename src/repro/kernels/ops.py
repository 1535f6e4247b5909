"""Jitted wrappers bridging :mod:`repro.core` to the Pallas kernels."""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp

from repro.core import graph as graphlib
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

  Restrictions (enforced by ``spmv._pallas_eligible`` / asserted here):
  single-leaf scalar messages, or ``[n, Q]`` lanes of a lanewise program;
  add/min/max reductions.
  """
  msg_leaves, msg_def = jax.tree_util.tree_flatten(msg)
  assert len(msg_leaves) == 1, "pallas path: single-leaf messages only"
  m = msg_leaves[0]
  assert m.ndim == 1 or (m.ndim == 2 and program.lanewise), (
      "pallas path: vector payloads must be lanes of a lanewise program")

  dpp = None
  if program.process_reads_dst:
    dp_leaves = jax.tree_util.tree_leaves(dst_prop)
    assert len(dp_leaves) == 1, "pallas path: single-leaf dst_prop only"
    with jax.named_scope(SCOPE_GATHER):
      dpp = dp_leaves[0][jnp.minimum(g.row_of, g.n - 1)]

  y_leaf, recv_packed = ell_spmv_pallas(
      g.cols, g.vals, g.mask, m, active, dpp,
      process=program.process_message, reduce_kind=program.reduce_kind,
      slot_rows=g.slot_rows, **kernel_kwargs)
  y_packed = jax.tree_util.tree_unflatten(msg_def, [y_leaf])

  y, recv = _unpermute(g, y_packed, recv_packed)
  return fold_split(g, y, recv, y_packed, recv_packed, program)
