"""Device-side decode of a chunk batch's index planes, shared by the scan
step (:mod:`repro.core.sem`) and the Pallas wave kernel
(:mod:`repro.kernels.sem_spmm`)."""
from __future__ import annotations

import jax.numpy as jnp


def decode_planes(meta, row_l, col_l, T: int):
    """Device mirror of :func:`repro.core.formats.decode_packed_planes`:
    upcast raw uint16/int32 planes; decode an optimized store's
    flattened-key deltas (a uint8 column plane marks packing, the row
    plane's width the 16- vs 24-bit delta mode; chunk bases ride in meta
    columns 4/5).  The dtype branch resolves at trace time, so the
    raw-store path keeps the exact jit graph it had before delta packing
    existed.  Integer-exact, so raw and packed stores of the same matrix
    produce bitwise-equal gathers."""
    if col_l.dtype == jnp.uint8:
        dk = (row_l.astype(jnp.int32) << 8) | col_l.astype(jnp.int32)
        k = meta[:, 4:5] * T + meta[:, 5:6] + jnp.cumsum(dk, axis=1)
        r = k // T
        c = k - r * T
        valid = jnp.arange(row_l.shape[1])[None, :] < meta[:, 3:4]
        r = jnp.where(valid, r, 0)
        c = jnp.where(valid, c, 0)
    else:
        r = row_l.astype(jnp.int32)
        c = col_l.astype(jnp.int32)
    return r, c
