"""Share of the arcs that the ELL container places in split rows.

``build_ell`` gives a vertex of in-degree d its home row and, past the slot
width W, ``ceil(d / W) - 1`` split rows whose results are folded into it
after the reduce over slots.  The metric is ``100 * sum(d - W over the
vertices with split rows) / sum(d)``, from the cell's arcs and the
program's own ``repro.core.graph.ell_split`` at ``build_ell``'s default
width, as the cell's container calls it.  None where the program has no
``ell_split`` (hub rows then go elsewhere), or the cell's container is not
the ELL.
"""

import numpy as np


def read(ctx):
  if ctx.config["graph"]["container"] != "ell" or not ctx.arcs.dst.size:
    return None
  try:
    from repro.core.graph import ell_split
  except ImportError:
    return None
  in_deg = np.bincount(ctx.arcs.dst, minlength=ctx.arcs.n)
  width, rows = ell_split(in_deg)
  split = np.where(rows > 1, in_deg - width, 0).sum()
  return 100.0 * float(split) / float(in_deg.sum())
