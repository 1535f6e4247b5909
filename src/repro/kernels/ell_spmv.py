"""Pallas TPU kernel: generalized ELL SpMV (the paper's hot loop).

The paper spends >80% of runtime in Algorithm 1 (generalized SpMV) and
optimizes it with cache-resident bitvectors, ``-ipo`` inlining of the user
functions, and load-balanced partitions.  The TPU translation:

* **Layout** — slot-major, degree-sorted ELL: ``cols/vals/mask[W, n_pad]``.
  Packed rows (destination vertices) lie along the 128-wide lane axis, so
  every tile is lane-dense whatever the slot width W, and the per-row
  reduction over slots is an elementwise combine across sublanes.
* **Gather** — ``msg[cols]`` is gathered by XLA outside the kernel: Mosaic
  has no general in-kernel vector gather, and keeping ``msg`` resident in
  VMEM does not scale past a few million sources.  The kernel fuses what
  follows the gather — PROCESS_MESSAGE, the validity mask and the REDUCE
  over slots — so per-edge results never round-trip through HBM.
* **Chunks** — a ``[n_src, K]`` payload (K batched queries of a *lanewise*
  program) runs in blocks of BQ lanes, and the slots in chunks: one gather
  and one kernel call per (chunk, block), so each gathered ``[BQ, CW, R]``
  temporary stays within :data:`GATHER_BYTES` at any graph size.
* **Row extents** — rows are sorted by in-degree, so slot s holds edges only
  in the first ``slot_rows[s]`` packed rows.  A chunk gathers and reduces
  the first R rows of each of its slots, R its first slot's extent, so a
  slot whose extent is shorter is fetched with padding.  :func:`chunk_plan`
  ends a chunk where the extents fall below :data:`EXTENT_CUT` of its R,
  which keeps that padding small on a power-law graph; on a regular graph
  chunks are as wide as the budgets allow.
* **Tiling** — each call's grid runs over row tiles of BR packed rows; a
  tile reduces its CW slots in VMEM into ``y[BQ, 1, BR]``.
* **Inlining** — the user's PROCESS_MESSAGE/REDUCE are traced straight into
  the kernel body (the ``-ipo`` effect, by construction).
* **Interpret mode** is chosen from the platform the program is lowered for
  (``lax.platform_dependent``): interpreted on CPU, compiled by Mosaic on
  TPU.  ``interpret=True/False`` forces one.
"""

from __future__ import annotations

import functools
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.semiring import MONOIDS
from repro.core.spmv import SCOPE_GATHER, SCOPE_KERNEL

Array = jax.Array

# Bytes of one gathered message chunk [BQ, CW, n_pad] (an HBM temporary; the
# TPU gather also stages a flat copy of its indices of the same size).
GATHER_BYTES = 1 << 28
# Target bytes of one message tile [BQ, CW, BR] in VMEM.
TILE_BYTES = 1 << 20
# A slot chunk ends before a slot whose row extent is below this share of
# the chunk's first extent (the gathers fetch every slot to that extent).
EXTENT_CUT = 7 / 8


def _kernel(m_ref, e_ref, v_ref, *refs, process, reduce_kind, out_dtype):
  """One row tile of one slot chunk: y[BQ, 1, BR] = REDUCE over the CW slots
  of ``where(valid, process(m, e, d), identity)``."""
  d_ref, y_ref = refs if len(refs) == 2 else (None, refs[0])
  m = m_ref[...]                                     # [BQ, CW, BR]
  e = jnp.broadcast_to(e_ref[...][None], m.shape)    # edge values
  d = (jnp.broadcast_to(d_ref[...], m.shape) if d_ref is not None
       else jnp.zeros(m.shape, m.dtype))             # dst property
  valid = v_ref[...] != 0                            # [CW, BR]
  r = process(m, e, d).astype(out_dtype)
  monoid = MONOIDS[reduce_kind]
  r = jnp.where(jnp.broadcast_to(valid[None], r.shape), r,
                monoid.identity(out_dtype))
  y_ref[...] = monoid.reduce(r, axis=1, keepdims=True)


def _pick_block(total: int, target: int, multiple: int) -> int:
  """Largest divisor of ``total`` that is ≤ target and a multiple of
  ``multiple`` (falls back to total)."""
  best = total
  for cand in range(multiple, min(target, total) + 1, multiple):
    if total % cand == 0:
      best = cand
  return best


def _lane_block(k: int, cap: int) -> int:
  """Lanes per call: all K if they fit ``cap``, else the largest multiple of 8
  dividing K that fits, else 1.  (Blocks of 2-7 lanes make XLA's TPU gather
  compile for tens of seconds at millions of rows.)"""
  if k <= cap:
    return k
  best = _pick_block(k, cap, 8)
  return best if best <= cap else 1


def _call(mg, vals, valid, dp, *, process, reduce_kind, out_dtype, br,
          interpret):
  bq, cw, n_pad = mg.shape
  in_specs = [pl.BlockSpec((bq, cw, br), lambda i: (0, 0, i)),
              pl.BlockSpec((cw, br), lambda i: (0, i)),
              pl.BlockSpec((cw, br), lambda i: (0, i))]
  args = [mg, vals, valid]
  if dp is not None:
    in_specs.append(pl.BlockSpec((dp.shape[0], 1, br), lambda i: (0, 0, i)))
    args.append(dp)
  if not interpret:
    # Operands in HBM, read by the kernel's own pipeline: XLA would stage a
    # small chunk into VMEM with copies outside the kernel, whose time in a
    # profile would then leave out the kernel's memory traffic.
    args = [pltpu.with_memory_space_constraint(a, pltpu.HBM) for a in args]
  kern = functools.partial(_kernel, process=process, reduce_kind=reduce_kind,
                           out_dtype=out_dtype)
  # Scoped here, inside the branch ``platform_dependent`` picks: its
  # branches do not inherit the caller's scope.
  with jax.named_scope(SCOPE_KERNEL):
    return pl.pallas_call(
        kern,
        grid=(n_pad // br,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bq, 1, br), lambda i: (0, 0, i)),
        out_shape=jax.ShapeDtypeStruct((bq, 1, n_pad), out_dtype),
        interpret=interpret,
        name="ell_spmv",
    )(*args)


def chunk_plan(slot_rows: Sequence[int], n_pad: int, lanes_per_block: int,
               itemsize: int, unit: int, block_slots: Optional[int] = None
               ) -> List[Tuple[int, int, int]]:
  """Slot chunks ``(s0, s1, r)``: slots ``[s0, s1)`` gathered and reduced
  over their first ``r`` packed rows.

  The chunks cover the slots of nonzero extent in order; ``r`` is the first
  slot's extent rounded up to ``unit`` rows.  A chunk ends at the first of:
  its width cap (``block_slots``, else as many slots as :data:`GATHER_BYTES`
  allows at ``r`` and :data:`TILE_BYTES` at one row unit, in multiples of
  8 above 8), and a slot whose extent is below :data:`EXTENT_CUT` of the
  first's.  Equal extents give chunks as wide as the cap.
  """
  rows = list(slot_rows)
  w = next((s for s, x in enumerate(rows) if x <= 0), len(rows))
  budget = GATHER_BYTES // itemsize         # elements of one gathered chunk
  chunks, s0 = [], 0
  while s0 < w:
    r = min(n_pad, -(-rows[s0] // unit) * unit)
    cap = block_slots or max(1, min(
        budget // (lanes_per_block * r),
        TILE_BYTES // (itemsize * lanes_per_block * unit)))
    cap = cap - cap % 8 if cap > 8 else cap  # sublane multiples compile faster
    end = min(w, s0 + cap)
    s1 = next((s for s in range(s0 + 1, end)
               if rows[s] < EXTENT_CUT * rows[s0]), end)
    chunks.append((s0, s1, r))
    s0 = s1
  return chunks


def ell_spmv_pallas(
    cols: Array, vals: Array, mask: Array, msg: Array, active: Array,
    dprop: Optional[Array] = None, *, process: Callable, reduce_kind: str,
    block_rows: Optional[int] = None, block_slots: Optional[int] = None,
    block_queries: Optional[int] = None,
    slot_rows: Optional[Sequence[int]] = None,
    interpret: Optional[bool] = None) -> Tuple[Array, Array]:
  """Generalized ELL SpMV / multi-query SpMM.

  Args:
    cols: int32[W, n_pad] source index of each (slot, packed row).
    vals: [W, n_pad] edge values.
    mask: bool[W, n_pad] slot validity.
    msg:  [n_src] scalar payloads, or [n_src, K] lanes of a lanewise program
      (K batched queries; lanes never mix).
    active: bool[n_src] source frontier.
    dprop: None, or destination properties in packed row order: [n_pad], or
      [n_pad, K] per lane.
    process: elementwise ``(m, e, d) -> r`` on same-shaped arrays — the
      per-edge PROCESS_MESSAGE of a vertex program; traced inline.
    reduce_kind: a kind of :data:`repro.core.semiring.MONOIDS` the kernel
      takes (``pallas``): add | min | max.
    block_rows: rows per kernel tile (BR; a multiple of 128 on TPU, or
      n_pad).
    block_slots: most slots per gather + kernel call (CW; chunks end
      earlier where the row extents fall, :func:`chunk_plan`).
    block_queries: lanes per gather + kernel call (BQ divides K).
    slot_rows: non-increasing static row extents: slot s holds edges only
      in packed rows ``[0, slot_rows[s])`` (None: in all rows).
    interpret: force the Pallas interpreter on/off (default: by platform).
  Returns:
    (y with msg's trailing shape over n_pad rows, recv bool[n_pad]).
  """
  w, n_pad = cols.shape
  lanes = msg if msg.ndim == 2 else msg[:, None]          # [n_src, K]
  n_src, k = lanes.shape
  dl = None if dprop is None else (
      dprop if dprop.ndim == 2 else dprop[:, None])       # [n_pad, Kd]
  out_dtype = jax.eval_shape(
      process, jax.ShapeDtypeStruct((), msg.dtype),
      jax.ShapeDtypeStruct((), vals.dtype),
      jax.ShapeDtypeStruct((), msg.dtype if dl is None else dl.dtype)).dtype

  monoid = MONOIDS[reduce_kind]
  rows = [n_pad] * w if slot_rows is None else list(slot_rows)
  isz = lanes.dtype.itemsize
  unit = block_rows or (128 if n_pad % 128 == 0 else n_pad)
  assert n_pad % unit == 0, f"block_rows {unit} must divide n_pad={n_pad}"
  budget = GATHER_BYTES // isz              # elements of one gathered chunk
  bq = block_queries or _lane_block(k, max(1, budget // max(rows[0], 1)))
  assert k % bq == 0, f"block_queries {bq} must divide K={k}"

  chunks = chunk_plan(rows, n_pad, bq, isz, unit, block_slots)

  if interpret is None:
    def kernel(*a, **kw):
      return jax.lax.platform_dependent(
          *a, cpu=functools.partial(_call, interpret=True, **kw),
          tpu=functools.partial(_call, interpret=False, **kw))
  else:
    kernel = functools.partial(_call, interpret=interpret)

  # Named scopes: the gathers (of messages, the frontier and dst
  # properties) apart from the kernel calls and their combine.
  nb = k // bq
  with jax.named_scope(SCOPE_GATHER):
    m_t = lanes.T.reshape(nb, bq, n_src)
    per_lane_d = dl is not None and dl.shape[1] > 1
    if per_lane_d:
      assert dl.shape[1] == k, f"dprop lanes {dl.shape[1]} != K={k}"
      d_t = dl.T.reshape(nb, bq, 1, n_pad)
    else:
      d_shared = None if dl is None else dl.T[:, None, :]  # [1, 1, n_pad]

  with jax.named_scope(SCOPE_KERNEL):
    y = jnp.full((nb, bq, 1, n_pad), monoid.identity(out_dtype), out_dtype)
    recv = jnp.zeros((n_pad,), bool)
  for s0, s1, r in chunks:
    with jax.named_scope(SCOPE_GATHER):
      c, e, msk = (a[s0:s1, :r] for a in (cols, vals, mask))  # [CW, R]
      valid = jnp.logical_and(msk, active[c])
      v8 = valid.astype(jnp.int8)
    br = block_rows or _pick_block(
        r, max(unit, TILE_BYTES // (isz * bq * (s1 - s0))), unit)
    call = functools.partial(kernel, process=process, reduce_kind=reduce_kind,
                             out_dtype=out_dtype, br=br)

    def block(mb, db, c=c, e=e, v8=v8, call=call, r=r):
      with jax.named_scope(SCOPE_GATHER):
        mg = jnp.take(mb, c, axis=1, mode="clip")
        db = None if db is None else db[..., :r]
      return call(mg, e, v8, db)

    if nb == 1:
      part = block(m_t[0], d_t[0] if per_lane_d else d_shared)[None]
    elif per_lane_d:
      part = jax.lax.map(lambda a: block(*a), (m_t, d_t))
    else:
      part = jax.lax.map(lambda mb: block(mb, d_shared), m_t)
    with jax.named_scope(SCOPE_KERNEL):
      y = y.at[..., :r].set(monoid.combine(y[..., :r], part))
      recv = recv.at[:r].set(jnp.logical_or(recv[:r],
                                            jnp.any(valid, axis=0)))
  with jax.named_scope(SCOPE_KERNEL):
    y = y.reshape(k, n_pad).T                             # [n_pad, K]
  return (y if msg.ndim == 2 else y[:, 0]), recv
