"""Pure-jnp oracles for the Pallas kernels (independent of repro.core)."""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array

_AXIS_RED = {"add": jnp.sum, "min": jnp.min, "max": jnp.max}


def _identity_scalar(kind: str, dtype):
  if kind == "add":
    return jnp.zeros((), dtype)
  if kind == "min":
    return (jnp.array(jnp.inf, dtype) if jnp.issubdtype(dtype, jnp.floating)
            else jnp.array(jnp.iinfo(dtype).max, dtype))
  if kind == "max":
    return (jnp.array(-jnp.inf, dtype) if jnp.issubdtype(dtype, jnp.floating)
            else jnp.array(jnp.iinfo(dtype).min, dtype))
  raise ValueError(kind)


def ell_spmv_ref(cols: Array, vals: Array, mask: Array, msg: Array,
                 active: Array, dprop: Optional[Array] = None, *,
                 process: Callable, reduce_kind: str) -> Tuple[Array, Array]:
  """Oracle for :func:`repro.kernels.ell_spmv.ell_spmv_pallas`.

  Same contract: slot-major ``cols/vals/mask[W, n_pad]``, msg ``[n_src]`` or
  ``[n_src, K]``, dprop None / ``[n_pad]`` / ``[n_pad, K]`` in packed row
  order; returns (y over n_pad rows with msg's trailing shape, recv bool).
  """
  m = msg[cols]                                    # [W, n_pad, (K)]
  valid = mask.astype(bool) & active.astype(bool)[cols]
  tail = (None,) * (msg.ndim - 1)
  e = jnp.broadcast_to(vals[(...,) + tail], m.shape)
  d = (jnp.zeros(m.shape, m.dtype) if dprop is None
       else jnp.broadcast_to(dprop[None], m.shape))
  r = process(m, e, d)
  ident = _identity_scalar(reduce_kind, r.dtype)
  r = jnp.where(valid[(...,) + tail], r, ident)
  return _AXIS_RED[reduce_kind](r, axis=0), jnp.any(valid, axis=0)
