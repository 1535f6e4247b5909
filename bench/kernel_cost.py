"""Bytes that a kernel call must move, from shapes alone.

Kept with the benchmark so that every change is measured against the same
count.  A roofline share is the least time the chip could take over the
kernel's measured time.  ``ell_spmv`` does one PROCESS and one REDUCE per
9 bytes it reads, so HBM bandwidth, not the operation peak, bounds it.
"""

from __future__ import annotations

from typing import Sequence

# The kernel's validity operand is one int8 per (slot, row).
MASK_BYTES = 1


def ell_spmv_bytes(slot_rows: Sequence[int], lanes: int, msg_bytes: int,
                   val_bytes: int, out_bytes: int) -> int:
  """HBM bytes of one ``ell_spmv`` SpMV over a degree-sorted ELL.

  Slot ``s`` holds edges only in its first ``slot_rows[s]`` packed rows, so
  the kernel's operands must bring, for each such (slot, row): one gathered
  message per lane, the edge value and the validity byte; and it must write
  one reduced value per lane for each row that holds any edge
  (``slot_rows[0]`` rows).  Rows the kernel reads beyond an extent (chunks
  are cut to whole row tiles) are padding and are not counted.
  """
  slots = sum(int(r) for r in slot_rows)
  per_slot = lanes * msg_bytes + val_bytes + MASK_BYTES
  rows_out = int(slot_rows[0]) if len(slot_rows) else 0
  return slots * per_slot + rows_out * lanes * out_bytes

