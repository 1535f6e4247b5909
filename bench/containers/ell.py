"""The program's ELL container: ``build_ell`` at its default width, hub rows
spilling to its COO tail.  The layout (row extents per slot, value bytes)
is what ``bench/kernel_cost.py`` counts a kernel call's bytes from."""

from repro.core import graph as G


def build(arcs, spec: dict):
  g = G.build_ell(arcs.src, arcs.dst, arcs.w, n=arcs.n)
  return g, {"slot_rows": g.slot_rows, "val_bytes": g.vals.dtype.itemsize}
