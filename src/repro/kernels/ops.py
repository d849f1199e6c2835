"""jit'd wrappers and per-tile dispatch for the SpMM Pallas wave kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.formats import ChunkedTiles
from repro.kernels.sem_spmm import LANE, spmm_tiles_acc


def use_interpreter() -> bool:
    """Whether Pallas kernels run in interpret mode — decided here, and only
    here, from the backend: interpreted on ``cpu`` (where there is nothing
    to compile them for), compiled on ``tpu``.  Any other backend has no
    Pallas path and is an error, not a silent fallback to the
    interpreter."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"no Pallas path for backend {backend!r}: the wave "
                       "kernel compiles for 'tpu' and is interpreted on "
                       "'cpu'")


def lane_multiple() -> int:
    """Dense-width multiple the kernel's operand and accumulator need: the
    compiled TPU target wants the 128-lane register width, the interpreter
    accepts any width."""
    return 1 if use_interpreter() else LANE


def pick_variant(T: int) -> str:
    """Per-matrix execution-path dispatch (the SCSR/COO hybrid analogue).

    Napkin math (v5e-class numbers): the MXU path spends ``2*C*T*p`` MACs per
    chunk at ~1e5 MAC/cycle -> ``2*C*T*p / 1e5`` cycles.  The gather path
    walks ``C`` dynamic rows; per-element dynamic gather/scatter sustains
    ~16 elem/cycle on the VPU -> ``C*p / 16`` cycles.  Crossover:
    ``2*T / 1e5 = 1/16``  =>  ``T ~ 3000``.  So the densify/MXU path wins for
    small tiles and the gather path for the paper's 16K tiles.  Threshold set
    at 2048 (hardware-aligned); never measured on a chip.  Takes the tile
    size ``T`` (the only statistic the decision needs) so both the one-shot
    path (a ChunkedTiles in memory) and the streaming engine (a TileStore
    header) can dispatch."""
    return "mxu" if T <= 2048 else "gather"


def spmm_pallas(ct: ChunkedTiles, x: jax.Array,
                variant: str | None = None) -> jax.Array:
    """out = A @ X via the Pallas kernel; A as ChunkedTiles, X (n, p).  The
    whole matrix is one wave of the streaming kernel into a zero
    accumulator."""
    variant = variant or pick_variant(ct.T)
    n, p = x.shape
    pw = p + (-p) % lane_multiple()
    x_pad = jnp.zeros((ct.padded_cols, pw), x.dtype).at[:n, :p].set(x)
    acc = jnp.zeros((ct.n_tile_rows, ct.T, pw), x.dtype)
    out = spmm_pallas_batch(jnp.asarray(ct.meta), ct.n_chunks,
                            jnp.asarray(ct.row_local),
                            jnp.asarray(ct.col_local), jnp.asarray(ct.vals),
                            x_pad, acc, T=ct.T, variant=variant)
    return out.reshape(-1, pw)[: ct.n_rows, :p]


@functools.partial(jax.jit, static_argnames=("T", "variant"),
                   donate_argnums=(6,))
def spmm_pallas_batch(meta, n_valid, rows, cols, vals, x_pad, out_blocks,
                      *, T: int, variant: str = "gather") -> jax.Array:
    """SEM-streaming step: apply one chunk batch read from the slow tier and
    accumulate into the donated ``out_blocks`` (n_tile_rows, T, p).

    The whole step is device-resident — the engine stages ``meta`` and the
    batch's valid-chunk count ``n_valid`` like any other plane, and the
    kernel (:func:`repro.kernels.sem_spmm.spmm_tiles_acc`) recomputes
    first-of-tile-row flags, skips fixed-shape tail pads, seeds every
    touched output window from the accumulator it aliases, and leaves
    untouched tile rows alone.  ``rows``/``cols`` may be uint16 (upcast on
    device) or an optimized store's uint8 deltas (decoded on device from
    the meta bases); ``vals is None`` denotes a binary matrix whose unit
    values are synthesized on device from chunk nnz."""
    n_tile_rows, _, p = out_blocks.shape
    n_valid = jnp.asarray(n_valid, jnp.int32).reshape(1)
    acc = out_blocks.reshape(n_tile_rows * T, p)
    out = spmm_tiles_acc(meta, n_valid, rows, cols, vals, x_pad, acc,
                         T=T, variant=variant, interpret=use_interpreter())
    return out.reshape(n_tile_rows, T, p)
