"""SpMM execution paths.

Three tiers, all computing ``out = A @ X`` for sparse ``A`` (n x n) and dense
``X`` (n x p):

* :func:`spmm_coo` — flat jnp scatter-add over COO arrays.  The oracle, and
  also the paper's *unblocked CSR baseline* stand-in for the Fig-12 ablation
  (no cache blocking: one giant scatter over the whole matrix).
* :func:`spmm_chunked` — the cache-blocked execution the paper describes:
  iterates tiles in (tile_row, tile_col) order with a fixed VMEM-sized
  working set per step, accumulating each output block locally and writing
  it once.  Pure jnp (lax.scan over chunks); numerically identical to the
  Pallas kernels in ``repro.kernels`` and used as their oracle at scale.
* ``repro.kernels.ops.spmm_pallas`` — the Pallas wave kernel (gather/VPU
  and densify/MXU variants) behind the same chunk layout, run as one
  streaming wave over every chunk.

All paths support generalized semirings except the MXU kernel (plus-times
only, as on real hardware).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import semiring as sr
from repro.core.formats import COO, ChunkedTiles


# ---------------------------------------------------------------------------
# Flat COO path (oracle / unblocked baseline)
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("n_rows", "semiring"))
def _spmm_coo_impl(rows, cols, vals, x, n_rows: int, semiring: str):
    ring = sr.SEMIRINGS[semiring]
    gathered = jnp.take(x, cols, axis=0)
    prod = ring.mul(vals[:, None], gathered)
    return ring.add_segment(prod, rows, n_rows)


def spmm_coo(a: COO, x: jax.Array, semiring: str = "plus_times") -> jax.Array:
    vals = (np.ones(a.nnz, np.float32) if a.vals is None
            else a.vals.astype(np.float32))
    return _spmm_coo_impl(jnp.asarray(a.rows), jnp.asarray(a.cols),
                          jnp.asarray(vals, x.dtype), x, a.n_rows,
                          semiring)


# ---------------------------------------------------------------------------
# Chunked (cache-blocked) path
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("T", "n_tile_rows", "semiring"))
def _spmm_chunked_impl(meta, row_l, col_l, vals, x_pad, T: int,
                       n_tile_rows: int, semiring: str):
    """lax.scan over chunks.  Each step's working set is one (T, p) block of
    X plus one chunk — the VMEM-sized unit the Pallas kernel streams.  The
    output accumulates into (n_tile_rows, T, p); each block is touched only
    by its own tile row's chunks (write-once per block in the kernel)."""
    ring = sr.SEMIRINGS[semiring]
    p = x_pad.shape[1]
    # Accept uint16 local indices (the on-disk SCSR width) — upcast on device.
    row_l = row_l.astype(jnp.int32)
    col_l = col_l.astype(jnp.int32)
    x_blocks = x_pad.reshape(-1, T, p)

    def step(out, chunk):
        m, r, c, v = chunk
        xb = x_blocks[m[1]]                       # (T, p) "HBM->VMEM" load
        gathered = jnp.take(xb, c, axis=0)        # (C, p)
        prod = ring.mul(v[:, None], gathered)
        # mask padding lanes (val==0 rows may alias row 0 in non-plus rings)
        valid = (jnp.arange(r.shape[0]) < m[3])[:, None]
        if semiring == "plus_times":
            contrib = jnp.where(valid, prod, 0.0)
            out = out.at[m[0]].add(
                jnp.zeros((T, p), x_pad.dtype).at[r].add(contrib))
        else:
            neutral = jnp.full_like(prod, ring.zero)
            prod = jnp.where(valid, prod, neutral)
            blk = ring.add_segment(prod, r, T)
            merged = ring.add_segment(
                jnp.concatenate([out[m[0]], blk], 0),
                jnp.tile(jnp.arange(T), 2), T)
            out = out.at[m[0]].set(merged)
        return out, None

    init = jnp.full((n_tile_rows, T, p), ring.zero, x_pad.dtype)
    out, _ = jax.lax.scan(step, init, (meta, row_l, col_l, vals))
    return out.reshape(n_tile_rows * T, p)


def spmm_chunked(ct: ChunkedTiles, x: jax.Array,
                 semiring: str = "plus_times") -> jax.Array:
    p = x.shape[1]
    x_pad = jnp.zeros((ct.padded_cols, p), x.dtype).at[: x.shape[0]].set(x)
    out = _spmm_chunked_impl(jnp.asarray(ct.meta), jnp.asarray(ct.row_local),
                             jnp.asarray(ct.col_local),
                             jnp.asarray(ct.vals, x.dtype), x_pad,
                             ct.T, ct.n_tile_rows, semiring)
    return out[: ct.n_rows]


def spmm(a, x: jax.Array, semiring: str = "plus_times",
         use_pallas: bool = False) -> jax.Array:
    """Dispatch on input format."""
    if isinstance(a, COO):
        return spmm_coo(a, x, semiring)
    if isinstance(a, ChunkedTiles):
        if use_pallas:
            from repro.kernels.ops import spmm_pallas
            assert semiring == "plus_times"
            return spmm_pallas(a, x)
        return spmm_chunked(a, x, semiring)
    raise TypeError(f"unsupported sparse format {type(a)}")
