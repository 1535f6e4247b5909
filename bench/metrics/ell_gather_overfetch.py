"""Share of the ELL path's gathered slots that lie past the row extents.

The message and frontier gathers fetch each slot chunk ``(s0, s1, r)`` of
``repro.kernels.ell_spmv.chunk_plan`` to its ``r`` rows; the extents hold
``sum(slot_rows)`` real (slot, row) entries.  The metric is
``100 * (sum((s1 - s0) * r) / sum(slot_rows) - 1)``.  None where the program
has no ``chunk_plan``, or the plan does not run the Pallas kernel.

The reader calls ``chunk_plan`` as ``ell_spmv_pallas`` does for the cells'
scalar messages: one lane, ``n`` padded to ``build_ell``'s default 128-row
blocks (the harness has released the container when readers run), the
configuration's ``precision`` as the message width, the plan's row tile or
128-row unit and its ``block_slots``.  ``bench/tests/test_overfetch.py``
holds it to the plan the kernel makes when the cells' programs are traced.
"""

import numpy as np

ROW_BLOCK = 128     # build_ell's default row_block


def read(ctx):
  rows = ctx.layout.get("slot_rows")
  if not rows or ctx.plan.backend != "pallas":
    return None
  try:
    from repro.kernels.ell_spmv import chunk_plan
  except ImportError:
    return None
  n_pad = -(-ctx.arcs.n // ROW_BLOCK) * ROW_BLOCK
  itemsize = np.dtype(ctx.config.get("precision", "float32")).itemsize
  unit = ctx.plan.block_rows or ROW_BLOCK
  plan = chunk_plan(rows, n_pad, 1, itemsize, unit, ctx.plan.block_slots)
  fetched = sum((s1 - s0) * r for s0, s1, r in plan)
  return 100.0 * (fetched / sum(rows) - 1.0)
