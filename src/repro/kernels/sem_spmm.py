"""Pallas TPU wave kernel for the semi-external SpMM stream (the paper's
compute hot-spot).

One call applies ONE chunk batch of the streaming pass and folds it into a
running accumulator: ``acc (n_tile_rows*T, p) += A_batch @ X``.  The grid
is (column block, chunk): the operand's columns split into 128-lane blocks
(a width that is no multiple of 128 is one block), and for each block one
step per chunk, chunks sorted by (tile_row, tile_col).  The output
BlockSpec is indexed by tile_row and column block only, so Pallas keeps
the output window in VMEM across every chunk of a tile row and writes it
to HBM once when the tile row changes — the paper's write-once,
merged-write discipline, enforced by the pipeline structure.  The
scalar-prefetched ``meta`` array is the static schedule that replaces the
paper's dynamic task queue.

Everything the engine's host shim used to do per batch happens on device:

* the index planes arrive as stored (uint16 SCSR lanes, or an optimized
  store's uint8 deltas) and :func:`repro.core.decode.decode_planes` turns
  them into int32 lanes inside the same jit — integer-exact, shared with
  the scan step;
* a binary matrix streams no value plane; its unit values are synthesized
  from the chunk nnz (``meta[:, 3]``);
* first-of-tile-row flags are recomputed from ``meta`` (a batch may start
  mid-tile-row, so the stored flag ``meta[:, 2]`` is ignored), and the
  first chunk of a tile row seeds its output window from the accumulator
  block, which the output aliases (``input_output_aliases``): tile rows the
  batch never touches keep their accumulated content;
* the engine's fixed-shape tail pads are skipped via the scalar-prefetched
  ``n_valid`` count.  ``n_valid`` — not a per-chunk nnz test — is the pad
  gate because an *empty tile row's* real chunk also has nnz == 0 yet must
  still run: it opens that row's output window.

Two variants, mirroring the paper's SCSR-vs-COO per-tile hybrid (§3.2):

* ``gather`` — the sparse path, O(nnz * p).  The chunk's lanes sit in
  SMEM; a scalar loop over the live lanes loads one X row per lane and
  scales it into a VMEM scratch, and a second loop adds each product into
  its output row.  Lanes are row-sorted within a chunk (the store's
  layout), so each output row accumulates ``0 + c1 + c2 + ...`` and is then
  added to its running value — the scan step's order, so the two engines
  agree bit for bit.
* ``mxu`` — the dense path, O(C * T * p).  The chunk is densified into
  one-hot matrices, one slab of the tile at a time (the largest power of
  two up to ``MXU_ROWS`` rows that divides T, so the slabs cover every
  row), and multiplied on the MXU at f32 precision (``HIGHEST``):
  ``gathered = E_c @ X`` then ``out += E_r·diag(v) @ gathered``.  It
  reassociates sums, so it is allclose to the scan step, not bit-identical.

Blocks: an X block and the output/accumulator windows are ``(T, bw)``
with ``bw`` one lane width, so VMEM does not grow with the wave's width;
the lane planes are ``(C,)`` SMEM rows (gather; C padded to a multiple of
1024, the TPU's tiling of a 1-D array) or ``(1, C)`` VMEM rows of a
``(n_chunks, 1, C)`` array (mxu) — both shapes the TPU lowering accepts,
where a ``(1, C)`` block of an ``(n_chunks, C)`` array is not.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.decode import decode_planes

LANE = 128       # TPU lane width: VMEM pads a block's last dim to it
SMEM_TILE = 1024  # TPU tiling of a 1-D 32-bit array: an SMEM block of one
#                   must hold a whole number of tiles
MXU_ROWS = 512   # most tile rows per one-hot slab of the MXU variant


# ---------------------------------------------------------------------------
# Kernel bodies
# ---------------------------------------------------------------------------
def _open_window(meta_ref, g, acc_ref, out_ref):
    """Seed the output window from the accumulator block at the first chunk
    of a tile row *within this batch* (``out_ref`` holds garbage until
    written — the alias guarantees HBM content, not VMEM content)."""
    prev = meta_ref[jnp.maximum(g - 1, 0), 0]
    first = jnp.logical_or(g == 0, meta_ref[g, 0] != prev)

    @pl.when(first)
    def _seed():
        out_ref[...] = acc_ref[...]


def _gather_body(meta_ref, nv_ref, rows_ref, cols_ref, vals_ref, x_ref,
                 acc_ref, out_ref, prod_ref):
    g = pl.program_id(1)

    @pl.when(g < nv_ref[0])
    def _step():
        _open_window(meta_ref, g, acc_ref, out_ref)
        nnz = meta_ref[g, 3]

        # Products first, sums second, in separate loops: the scan step
        # rounds every product before it is added, and one loop body would
        # let a backend contract ``s + v * x`` into a fused multiply-add.
        def scale(i, carry):
            prod_ref[pl.ds(i, 1), :] = (
                vals_ref[i] * x_ref[pl.ds(cols_ref[i], 1), :]
            ).astype(prod_ref.dtype)
            return carry

        def accumulate(i, carry):
            # (prev row, row value before this chunk, chunk's running sum)
            prev, base, s = carry
            r = rows_ref[i]
            new = r != prev
            base = jnp.where(new, out_ref[pl.ds(r, 1), :], base)
            s = (jnp.where(new, 0.0, s)
                 + prod_ref[pl.ds(i, 1), :].astype(s.dtype))
            out_ref[pl.ds(r, 1), :] = base + s
            return r, base, s

        jax.lax.fori_loop(0, nnz, scale, 0)
        zero = jnp.zeros((1, out_ref.shape[1]), out_ref.dtype)
        jax.lax.fori_loop(0, nnz, accumulate, (jnp.int32(-1), zero, zero))


def _mxu_body(meta_ref, nv_ref, rows_ref, cols_ref, vals_ref, x_ref, acc_ref,
              out_ref, *, tk: int):
    g = pl.program_id(1)

    @pl.when(g < nv_ref[0])
    def _step():
        T, p = out_ref.shape
        C = rows_ref.shape[1]
        rows, cols = rows_ref[...], cols_ref[...]           # (1, C) lanes
        live = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1) < meta_ref[g, 3]
        vals = jnp.where(live, vals_ref[...], 0.0)

        def slab(k):
            k0 = pl.multiple_of(k * tk, tk)
            return k0, jax.lax.broadcasted_iota(jnp.int32, (tk, C), 0) + k0

        def gather(k, acc):
            k0, iota = slab(k)
            onehot = (iota == cols).astype(x_ref.dtype)     # (tk, C)
            return acc + jax.lax.dot_general(
                onehot, x_ref[pl.ds(k0, tk), :], (((0,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)

        gathered = jax.lax.fori_loop(0, T // tk, gather,
                                     jnp.zeros((C, p), jnp.float32))
        _open_window(meta_ref, g, acc_ref, out_ref)

        def scatter(k, carry):
            k0, iota = slab(k)
            w = jnp.where(iota == rows, vals, 0.0)          # E_r·diag(v)
            blk = jnp.dot(w, gathered, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
            out_ref[pl.ds(k0, tk), :] += blk.astype(out_ref.dtype)
            return carry

        jax.lax.fori_loop(0, T // tk, scatter, 0)


# ---------------------------------------------------------------------------
# pallas_call wrapper
# ---------------------------------------------------------------------------
def _check_variant(variant: str) -> None:
    """Fail loudly on a typo'd variant: a silent fall-through to the MXU
    path would make a caller expecting the gather path's bit-exactness
    chase float drift instead."""
    if variant not in ("gather", "mxu"):
        raise ValueError(f"unknown kernel variant {variant!r}: "
                         "expected 'gather' or 'mxu'")


def spmm_tiles_acc(meta, n_valid, row_local, col_local, vals, x_pad, acc, *,
                   T: int, variant: str, interpret: bool):
    """One SEM chunk batch, fully device-resident: ``acc (n_tile_rows*T, p)
    += A_batch @ x_pad``, returned with only the batch's tile rows changed.

    ``meta`` is the scalar-prefetched schedule, ``n_valid (1,) int32`` the
    count of real chunks (the rest are fixed-shape tail pads, skipped; a
    pad replicates the last real chunk's tile coordinates so it never opens
    an unseeded output window).  ``vals is None`` denotes a binary matrix.
    ``acc`` is aliased to the output: callers hand it over (donate it) and
    use the result instead.  ``interpret`` runs the kernel through the
    Pallas interpreter (the CPU backend) instead of compiling it."""
    _check_variant(variant)
    n_chunks, C = row_local.shape
    p = x_pad.shape[1]
    # Column blocks of one lane width each, so VMEM stays the same however
    # wide the wave is.  A width that is no multiple of 128 (the engine
    # lane-pads p when compiled) is a single block.
    bw = LANE if p % LANE == 0 else p
    rows, cols = decode_planes(meta, row_local, col_local, T)
    if vals is None:
        lanes = jnp.arange(C, dtype=jnp.int32)[None, :]
        vals = (lanes < meta[:, 3:4]).astype(jnp.float32)
    planes = (rows, cols, vals.astype(jnp.float32))
    if variant == "gather":
        # lanes past C are padding: the lane loops stop at the chunk's nnz
        cp = C + (-C) % SMEM_TILE
        planes = tuple(jnp.pad(a, ((0, 0), (0, cp - C))).reshape(-1)
                       for a in planes)
        lane_spec = pl.BlockSpec((cp,), lambda j, g, m, nv: (g,),
                                 memory_space=pltpu.SMEM)
        body = _gather_body
        scratch = [pltpu.VMEM((C, bw), jnp.float32)]      # lane products
    else:
        planes = tuple(a.reshape(n_chunks, 1, C) for a in planes)
        lane_spec = pl.BlockSpec((None, 1, C), lambda j, g, m, nv: (g, 0, 0))
        # slabs tile T exactly: the largest power of two up to MXU_ROWS
        # that divides it
        body = functools.partial(_mxu_body, tk=math.gcd(T, MXU_ROWS))
        scratch = []

    def blk_of(col):
        return pl.BlockSpec((T, bw), lambda j, g, m, nv: (m[g, col], j))

    # X block + accumulator + output windows, each double-buffered and
    # lane-padded in VMEM, plus headroom for the gather variant's product
    # scratch and the MXU variant's one-hot slabs.
    vmem = 6 * T * max(bw, LANE) * 4 + (16 << 20)
    operands = (meta, n_valid) + planes + (x_pad, acc)
    return pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(p // bw, n_chunks),
            in_specs=[lane_spec] * 3 + [blk_of(1), blk_of(0)],
            out_specs=blk_of(0),
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct(acc.shape, acc.dtype),
        # the alias index counts the scalar-prefetch operands: acc is last
        input_output_aliases={len(operands) - 1: 0},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem),
        interpret=interpret,
    )(*operands)
