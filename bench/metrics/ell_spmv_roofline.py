"""Share of the HBM roofline that the ``ell_spmv`` Pallas kernel reaches.

Bytes: :func:`bench.kernel_cost.ell_spmv_bytes` of the ELL container's row
extents, once per superstep of the traced window (every superstep sweeps
every slot, whatever the frontier).  Time: the summed device time of the
trace's operations named after the kernel.  The kernel does one PROCESS and
one REDUCE per gathered value, far below the chip's operation peak, so the
HBM bandwidth bounds it.
"""

from bench.kernel_cost import ell_spmv_bytes

KERNEL = "ell_spmv"
F32 = 4


def read(ctx):
  rows = ctx.layout.get("slot_rows")
  steps = ctx.measures.get("supersteps", 0)
  if ctx.summary is None or not rows or not steps or not ctx.peaks:
    return None
  seconds = sum(t for name, t in ctx.summary.op_seconds.items()
                if KERNEL in name)
  if seconds <= 0:
    return None
  moved = ell_spmv_bytes(rows, lanes=1, msg_bytes=F32,
                         val_bytes=ctx.layout["val_bytes"], out_bytes=F32)
  return 100.0 * moved * steps / ctx.peaks["hbm_bytes_per_s"] / seconds
