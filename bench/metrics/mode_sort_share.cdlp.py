"""Share of the device's busy time in the COO ``mode`` reduce.

The operations of ``repro.core.spmv.segment_mode`` (the sort of every arc's
(destination, label) pair, the run detection, the scatters and the gather
of the arg-max) carry the named scope ``graphmat/spmv/mode`` in their
``op_name``, which the trace summary does not keep: on a v5e the sort is
``sort.<n>``, but the scatters and the gather are fusions named like any
other.  So the reader compiles the cell's CDLP program again for the
container's shapes (JAX's compilation cache gives back the program the run
compiled, and XLA names its operations the same way each time), takes the
names of the operations whose ``op_name`` holds the scope, and sums their
device time in the traced window.  The metric is 100 x that time over the
busy time; None where the program has no CDLP, or no such operation ran.
Operations the compiler builds with no ``op_name`` (on a v5e, the pieces of
the ``cummax``'s reduce-window) are left out, as ``bench/scopes.py``
leaves them out of the scope.
"""

import re

SCOPE = "graphmat/spmv/mode"
_INSTR = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*op_name="([^"]*)"')


def mode_ops(ctx):
  """Names of the compiled CDLP program's operations under the scope."""
  import jax
  import jax.numpy as jnp
  from repro.algos.cdlp import _cdlp_jit
  from repro.core.graph import CooGraph

  def spec(size, dtype):
    return jax.ShapeDtypeStruct((size,), dtype)
  cap, n = ctx.config["graph"]["capacity"], ctx.arcs.n
  g = CooGraph(n, spec(cap, jnp.int32), spec(cap, jnp.int32),
               spec(cap, jnp.float32), spec(cap, jnp.bool_),
               spec(n, jnp.int32), spec(n, jnp.int32))
  text = _cdlp_jit.lower(g, num_iters=int(ctx.config["cdlp"]["iterations"]),
                         backend=ctx.plan).compile().as_text()
  return {m.group(1) for m in map(_INSTR.match, text.splitlines())
          if m and SCOPE in m.group(2)}


def read(ctx):
  s = ctx.summary
  if s is None or s.busy_s <= 0:
    return None
  try:
    names = mode_ops(ctx)
  except ImportError:
    return None
  seconds = sum(t for name, t in s.op_seconds.items() if name in names)
  if seconds <= 0:
    return None
  return 100.0 * seconds / s.busy_s
