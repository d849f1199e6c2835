"""The semi-external-memory SpMM executor (the paper's core system).

Data placement policy (paper §3.1/§3.6):
* the sparse matrix always lives on the slow tier and is *streamed*;
* the input dense matrix — or as many of its columns as fit the memory
  budget — lives in fast memory (``IO_in = ncp/M' * [E - (M - M')]`` is
  minimized by spending memory on dense columns, not on caching the sparse
  matrix, because E > M);
* the output is buffered per tile-row block and written at most once.

``SEMSpMM.multiply`` handles all three regimes:
1. X fits in memory, output fits in memory  -> one streaming pass, in-memory out.
2. X fits, output streamed                  -> one pass, write-once out blocks.
3. X wider than budget                      -> vertical partitioning: one
   streaming pass of the sparse matrix per column slice (paper §3.3/§5.3).

``mode="im"`` keeps the sparse matrix in memory (IM-SpMM) — the paper's
own overhead-quantification baseline.

The streaming pass is a pipelined engine (the paper's premise that SEM
reaches ~100% of in-memory speed by hiding SSD latency behind compute,
carried through every stage, not just the disk read):

* **zero-copy reads** — batches arrive as uint16 strided views into the
  store's persistent memmap (``TileStore.read_batch_raw``), faulted in by
  the prefetch thread;
* **device-side decode** — the uint16 indices are shipped to the device
  as-is and upcast inside the jitted step, halving host->device index
  traffic (the SCSR 2-byte saving survives the whole pipeline); binary
  matrices ship no values at all (synthesized on device from chunk nnz);
* **overlapped staging** — batch k+1 is ``jax.device_put`` while batch k's
  kernel runs (async dispatch); the donated accumulator is only
  ``block_until_ready`` at pass end.  ``IOStats.h2d_bytes`` /
  ``overlap_batches`` expose the traffic and overlap for benchmarks;
* **fixed-shape batches** — the tail batch is padded to ``chunk_batch``
  with zero-nnz chunks so each jitted step compiles exactly once per
  (C, T, p);
* **pluggable device step** — ``use_pallas=True`` swaps the scan-based
  batch step for the Pallas wave kernel
  (:func:`repro.kernels.ops.spmm_pallas_batch`): first-of-tile-row flags
  are recomputed in-kernel from the scalar-prefetched meta, tail pads are
  skipped via a staged ``n_valid`` count, and the kernel accumulates
  straight into the donated output blocks it aliases — the gather variant
  is bit-identical to the scan step, and both share the same staging,
  overlap, h2d accounting, boundary hooks, and sharding.
  ``pallas_variant`` picks gather/VPU vs densify/MXU (``pick_variant`` by
  default).  The backend decides how the kernel runs
  (:func:`repro.kernels.ops.use_interpreter`): compiled on a TPU,
  interpreted on the CPU backend.

The pass is *elastic*: ``multiply(x, boundary_hook=...)`` invokes the hook
at every chunk-batch boundary with a :class:`PassBoundary` through which a
caller may rewrite operand columns mid-pass (shape-preserving, so the jit
entry is reused) and read the accumulator's completed tile-row prefix.
The serving scheduler builds mid-pass tenant admission on exactly this:
a newcomer's columns join the staged X at a boundary, and the tile rows
streamed after that boundary accumulate its partial first result.
"""
from __future__ import annotations

import dataclasses
import threading
from functools import partial
from typing import Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.semiring import PLUS_TIMES, SEMIRINGS, Semiring
from repro.io.storage import (DenseStore, GraphHandle, IOStats, TileStore,
                              UpdateBatch)
from repro.core.decode import decode_planes
from repro.trace import span

# Sentinel for "no per-pass cache override": callers that share one executor
# (the serving fleet's waves) pass their own budget slice per multiply;
# ``None`` must stay expressible as "explicitly uncached".
_CACHE_UNSET = object()


@dataclasses.dataclass
class SEMConfig:
    memory_budget_bytes: int = 1 << 30
    chunk_batch: int = 256        # chunks per I/O (large sequential reads)
    prefetch: int = 2             # async prefetch depth
    use_async: bool = True        # paper's async I/O + polling
    use_pallas: bool = False      # Pallas wave kernel as the engine backend
    pallas_variant: Optional[str] = None  # "gather" | "mxu";
    #                               None -> kernels.ops.pick_variant(T)
    decode_on_device: bool = True  # ship uint16 indices, upcast on device
    overlap: bool = True          # stage batch k+1 while batch k computes
    fixed_shape: bool = True      # pad the tail batch to chunk_batch


def _scan_batch(meta, row_l, col_l, vals, x_pad, out_blocks, T: int):
    """Trace-time body of the plus-times batch step, shared by the plain
    jit entry and the delta-fused one."""
    row_l, col_l = decode_planes(meta, row_l, col_l, T)
    x_blocks = x_pad.reshape(-1, T, x_pad.shape[1])

    def step(out, chunk):
        m, r, c, v = chunk
        gathered = jnp.take(x_blocks[m[1]], c, axis=0)
        contrib = v[:, None] * gathered
        blk = jnp.zeros((T, x_pad.shape[1]), x_pad.dtype).at[r].add(contrib)
        return out.at[m[0]].add(blk), None

    out_blocks, _ = jax.lax.scan(step, out_blocks, (meta, row_l, col_l, vals))
    return out_blocks


def _scan_batch_binary(meta, row_l, col_l, x_pad, out_blocks, T: int):
    """Trace-time body of the binary-matrix batch step."""
    row_l, col_l = decode_planes(meta, row_l, col_l, T)
    x_blocks = x_pad.reshape(-1, T, x_pad.shape[1])
    lanes = jnp.arange(row_l.shape[1])

    def step(out, chunk):
        m, r, c = chunk
        gathered = jnp.take(x_blocks[m[1]], c, axis=0)
        contrib = jnp.where((lanes < m[3])[:, None], gathered, 0.0)
        blk = jnp.zeros((T, x_pad.shape[1]), x_pad.dtype).at[r].add(contrib)
        return out.at[m[0]].add(blk), None

    out_blocks, _ = jax.lax.scan(step, out_blocks, (meta, row_l, col_l))
    return out_blocks


def _scan_batch_ring(meta, row_l, col_l, vals, x_pad, out_blocks, T: int,
                     ring: Semiring):
    """Trace-time body of the general-semiring batch step."""
    row_l, col_l = decode_planes(meta, row_l, col_l, T)
    x_blocks = x_pad.reshape(-1, T, x_pad.shape[1])
    lanes = jnp.arange(row_l.shape[1])
    zero = jnp.float32(ring.zero)

    if vals is None:
        def step(out, chunk):
            m, r, c = chunk
            gathered = jnp.take(x_blocks[m[1]], c, axis=0)
            contrib = ring.mul(jnp.float32(1.0), gathered)
            contrib = jnp.where((lanes < m[3])[:, None], contrib, zero)
            blk = ring.add_segment(contrib, r, T)
            return getattr(out.at[m[0]], ring.scatter)(blk), None
        xs = (meta, row_l, col_l)
    else:
        def step(out, chunk):
            m, r, c, v = chunk
            gathered = jnp.take(x_blocks[m[1]], c, axis=0)
            contrib = ring.mul(v[:, None], gathered)
            contrib = jnp.where((lanes < m[3])[:, None], contrib, zero)
            blk = ring.add_segment(contrib, r, T)
            return getattr(out.at[m[0]], ring.scatter)(blk), None
        xs = (meta, row_l, col_l, vals)

    out_blocks, _ = jax.lax.scan(step, out_blocks, xs)
    return out_blocks


@partial(jax.jit, static_argnames=("n_tile_rows", "T"))
def _delta_acc(rows, cols, vals, nv, x_pad, n_tile_rows: int, T: int):
    """Pass-level delta accumulator: ONE scatter of the staged snapshot
    (COO, engine coordinates, padded to a fixed floor) against the current
    operand — per-batch application then folds tile-row windows of this
    block with a dense masked add, so the scatter cost is paid once per
    pass, not once per batch.  The base fill and pad lanes are ``-0.0``:
    for every float ``f`` (including both zeros), ``f + (-0.0) == f``
    bitwise, so untouched entries are invisible even under bit-identity
    comparison — a ``+0.0`` fill would flip a ``-0.0`` accumulator entry
    to ``+0.0``."""
    lanes = jnp.arange(rows.shape[0])
    gathered = jnp.take(x_pad, cols, axis=0) * vals[:, None]
    gathered = jnp.where((lanes < nv)[:, None], gathered, -0.0)
    tr = rows // T
    dacc = jnp.full((n_tile_rows, T, x_pad.shape[1]), -0.0, x_pad.dtype)
    return dacc.at[tr, rows - tr * T].add(gathered)


@partial(jax.jit, static_argnames=("n_tile_rows", "T", "ring_name"))
def _delta_acc_ring(rows, cols, vals, nv, x_pad, n_tile_rows: int, T: int,
                    ring_name: str):
    """Ring variant of :func:`_delta_acc` (insert-only deltas: deletions
    are carried as negated values, which only cancel under plus-times —
    the caller rejects delete-carrying logs for other rings).  The base
    fill and pad lanes carry the ring's additive identity."""
    ring = SEMIRINGS[ring_name]
    lanes = jnp.arange(rows.shape[0])
    gathered = ring.mul(vals[:, None], jnp.take(x_pad, cols, axis=0))
    gathered = jnp.where((lanes < nv)[:, None], gathered,
                         jnp.float32(ring.zero))
    tr = rows // T
    dacc = jnp.full((n_tile_rows, T, x_pad.shape[1]),
                    jnp.float32(ring.zero), x_pad.dtype)
    return getattr(dacc.at[tr, rows - tr * T], ring.scatter)(gathered)


# The ring's cross-chunk ``.at[...]`` scatter name doubles as its
# elementwise fold for delta-accumulator blocks.
_RING_FOLD = {"add": jnp.add, "min": jnp.minimum, "max": jnp.maximum}


def _fold_delta(out_blocks, dacc, tr_lo, tr_hi):
    """Fold tile rows ``[tr_lo, tr_hi)`` of the pass's delta accumulator
    into the output — a dense masked add (vectorized, no scatter), so the
    per-batch cost of the overlay is O(rows) elementwise work.  Rows
    outside the window add ``-0.0``: bitwise invisible."""
    tr = jnp.arange(dacc.shape[0])
    mask = ((tr >= tr_lo) & (tr < tr_hi))[:, None, None]
    return out_blocks + jnp.where(mask, dacc, -0.0)


def _fold_delta_ring(out_blocks, dacc, tr_lo, tr_hi, ring: Semiring):
    """Ring variant of :func:`_fold_delta`: out-of-window rows fold the
    ring's additive identity (a bitwise no-op under the ring's combine)."""
    tr = jnp.arange(dacc.shape[0])
    mask = ((tr >= tr_lo) & (tr < tr_hi))[:, None, None]
    return _RING_FOLD[ring.scatter](
        out_blocks, jnp.where(mask, dacc, jnp.float32(ring.zero)))


@partial(jax.jit, static_argnames=("T", "semiring"), donate_argnums=(5,))
def _batch_step(meta, row_l, col_l, vals, x_pad, out_blocks, T: int,
                semiring: str = "plus_times"):
    """Apply one batch of chunks: out_blocks (n_tile_rows, T, p) += A_batch @ X.
    Accepts uint16/int32 local indices or uint8 delta planes; the upcast
    (or cumsum decode) happens here, on device (jit specializes per input
    dtype)."""
    return _scan_batch(meta, row_l, col_l, vals, x_pad, out_blocks, T)


@partial(jax.jit, static_argnames=("T",), donate_argnums=(4,))
def _batch_step_binary(meta, row_l, col_l, x_pad, out_blocks, T: int):
    """Binary-matrix step: no values are streamed or staged at all — a lane
    contributes 1.0 iff its index is below the chunk's nnz (device-side
    synthesis of what the decoded path materialized on the host)."""
    return _scan_batch_binary(meta, row_l, col_l, x_pad, out_blocks, T)


@partial(jax.jit, static_argnames=("T", "ring_name"), donate_argnums=(5,))
def _batch_step_ring(meta, row_l, col_l, vals, x_pad, out_blocks, T: int,
                     ring_name: str):
    """General-semiring batch step.  Unlike :func:`_batch_step` (which
    relies on zero-valued invalid lanes annihilating under plus-times),
    every lane is explicitly masked to the ring's additive identity —
    a zero value does NOT annihilate under min-plus.  Chunks of one tile
    row are folded into the accumulator with the ring's scatter op, and
    binary stores synthesize a unit weight per valid lane at trace time."""
    return _scan_batch_ring(meta, row_l, col_l, vals, x_pad, out_blocks, T,
                            SEMIRINGS[ring_name])


@partial(jax.jit, static_argnames=("T",), donate_argnums=(8,))
def _batch_step_delta(meta, row_l, col_l, vals, dacc, tr_lo, tr_hi,
                      x_pad, out_blocks, T: int):
    """Batch step chased by its delta fold in ONE dispatch.  A churny pass
    runs every batch through this entry instead of paying a second
    per-batch dispatch (and its host round-trip) for the overlay; the fold
    runs after the scan, so the bits match the unfused step-then-delta
    sequence exactly."""
    out_blocks = _scan_batch(meta, row_l, col_l, vals, x_pad, out_blocks, T)
    return _fold_delta(out_blocks, dacc, tr_lo, tr_hi)


@partial(jax.jit, static_argnames=("T",), donate_argnums=(7,))
def _batch_step_binary_delta(meta, row_l, col_l, dacc, tr_lo, tr_hi,
                             x_pad, out_blocks, T: int):
    """Binary-matrix variant of :func:`_batch_step_delta` (the overlay
    itself always carries explicit values — inserts may be weighted even
    when the base store is binary)."""
    out_blocks = _scan_batch_binary(meta, row_l, col_l, x_pad, out_blocks, T)
    return _fold_delta(out_blocks, dacc, tr_lo, tr_hi)


@partial(jax.jit, static_argnames=("T", "ring_name"), donate_argnums=(8,))
def _batch_step_ring_delta(meta, row_l, col_l, vals, dacc, tr_lo, tr_hi,
                           x_pad, out_blocks, T: int, ring_name: str):
    """General-semiring variant of :func:`_batch_step_delta`."""
    ring = SEMIRINGS[ring_name]
    out_blocks = _scan_batch_ring(meta, row_l, col_l, vals, x_pad,
                                  out_blocks, T, ring)
    return _fold_delta_ring(out_blocks, dacc, tr_lo, tr_hi, ring)


@partial(jax.jit, donate_argnums=(0,))
def _delta_fold(out_blocks, dacc, tr_lo, tr_hi):
    """Standalone delta-fold dispatch — the Pallas path's chase step (the
    wave kernel cannot absorb the fold), skipped for batches whose
    tile-row window is empty."""
    return _fold_delta(out_blocks, dacc, tr_lo, tr_hi)


class PassBoundary:
    """Mid-pass control point handed to ``boundary_hook`` before each chunk
    batch is dispatched.

    ``chunk_start`` is the index of the first chunk of the *next* batch, in
    this executor's chunk space; every chunk below it has already been
    dispatched against the operand columns staged at the time.  Chunks are
    laid out in (tile_row, tile_col) order, so chunks ``< chunk_start``
    touch only tile rows below the first row that starts at or after the
    boundary — which is what makes column rewrites here composable: a
    column written at this boundary receives bit-exact contributions for
    every tile row whose chunks all lie at or after ``chunk_start``.
    """

    def __init__(self, sem: "SEMSpMM", chunk_start: int, x_pad: jax.Array,
                 out: jax.Array):
        self.sem = sem
        self.chunk_start = chunk_start
        self.x_pad = x_pad
        self.out = out

    def write_columns(self, c0: int, cols: np.ndarray) -> None:
        """Replace operand columns ``[c0, c0+w)`` from this batch onward.
        Shape- and dtype-preserving, so subsequent steps hit the same jit
        entry the pass started with."""
        cols = np.asarray(cols, np.float32)
        if cols.ndim == 1:
            cols = cols[:, None]
        pad = self.sem.padded_cols
        if cols.shape[0] != pad:
            full = np.zeros((pad, cols.shape[1]), np.float32)
            full[: cols.shape[0]] = cols
            cols = full
        # an optimized store's engine column space is relabeled; the caller
        # writes user-space columns, so relabel here (no-op on raw stores)
        cols = self.sem.store.apply_col_perm(cols)
        dev = jax.device_put(jnp.asarray(cols), self.sem.device)
        self.sem.store.stats.add_h2d(dev.nbytes)
        self.x_pad = self.x_pad.at[:, c0:c0 + cols.shape[1]].set(dev)

    def read_output(self, n_tile_rows: int, c0: int, c1: int) -> np.ndarray:
        """Materialize accumulator tile rows ``[0, n_tile_rows)`` for columns
        ``[c0, c1)`` — every batch before this boundary applied.  Blocks on
        the in-flight steps (the price of mid-pass delivery)."""
        if n_tile_rows <= 0:
            return np.empty((0, c1 - c0), np.float32)
        blk = np.asarray(self.out[:n_tile_rows, :, c0:c1])
        self.sem.store.stats.add_d2h(blk.nbytes)
        n = min(n_tile_rows * self.sem.T, self.sem.n_rows)
        return blk.reshape(n_tile_rows * self.sem.T, c1 - c0)[:n]


@partial(jax.jit, donate_argnums=(0,))
def _zero_acc(out_blocks):
    """In-place zero of a donated accumulator (reused across vertical
    slices instead of allocating a fresh one per slice)."""
    return jnp.zeros_like(out_blocks)


@partial(jax.jit, static_argnames=("fill",), donate_argnums=(0,))
def _fill_acc(out_blocks, fill: float):
    """Ring counterpart of :func:`_zero_acc`: reset a donated accumulator
    to the ring's additive identity (inf for min-plus)."""
    return jnp.full_like(out_blocks, fill)


class SEMSpMM:
    """Semi-external-memory SpMM over a :class:`TileStore`."""

    def __init__(self, store: TileStore, config: Optional[SEMConfig] = None,
                 mode: str = "sem", cache=None, device=None):
        assert mode in ("sem", "im")
        self.store = store
        self.cfg = config or SEMConfig()
        self.mode = mode
        h = store.header
        self.n_rows, self.n_cols, self.T = h["n_rows"], h["n_cols"], h["T"]
        self.n_tile_rows = -(-self.n_rows // self.T)
        self.padded_cols = (-(-self.n_cols // self.T)) * self.T
        self._cached = None
        # Optional device pinning (sharded scans place one shard per device;
        # None = the backend default).
        self.device = device
        # Optional hot-chunk cache (duck-typed, see runtime/cache.py): pins
        # chunk batches in leftover memory, making this executor a hybrid
        # between pure-streaming SEM and fully-resident IM.
        self.cache = cache
        # ``passes`` counts streaming passes over the sparse matrix (the
        # serving scheduler's amortization accounting builds on it).
        # Concurrent serving waves may multiply through one executor at
        # once, so the increment is lock-protected like the IOStats
        # counters (a bare += can drop a pass under that interleaving).
        self.passes = 0
        self._passes_lock = threading.Lock()
        # Mutation surface: lazily attaches a GraphHandle on first
        # apply_updates (a frozen executor pays nothing for mutability).
        self._mut_lock = threading.Lock()
        # Version the last streaming pass was snapshotted at (0 = no delta
        # log / frozen store) — schedulers stamp PassReports from it.
        self.last_pass_version = 0
        # chunk_tile_rows() cache, keyed by (generation, n_chunks): a
        # compaction install rewrites the chunk layout under the same path.
        self._trow_key = None
        self._trow_cache = None
        if mode == "im":  # IM-SpMM: sparse matrix resident in memory
            self._cached = list(store.stream(self.cfg.chunk_batch,
                                             use_async=False))

    # -- mutation surface (the Mutable protocol) ----------------------------
    @property
    def version(self) -> int:
        """Graph version this executor serves (0 when frozen)."""
        return self.store.version

    @property
    def delta_nnz(self) -> int:
        """Consolidated entries in the delta overlay awaiting compaction."""
        dl = self.store.delta_log
        return 0 if dl is None else dl.nnz

    @property
    def graph_handle(self) -> Optional[GraphHandle]:
        return self.store.handle

    def apply_updates(self, batch: UpdateBatch) -> int:
        """Append an edge-update batch to the graph's delta log, lazily
        creating the :class:`GraphHandle` on first use; returns the new
        version.  Subsequent passes snapshot the log at pass start, so a
        pass is internally consistent and the flip lands at a pass
        boundary."""
        with self._mut_lock:
            if self.store.handle is None:
                if self.store._delta_src is not None:
                    raise ValueError(
                        "apply_updates must go through the root store's "
                        "executor, not a row-partitioned shard view")
                GraphHandle([self.store])
        return self.store.handle.apply_updates(batch)

    # -- the pipelined streaming pass ---------------------------------------
    def _use_raw(self) -> bool:
        return self.cfg.decode_on_device and self._cached is None

    def _prepare_x(self, x) -> jax.Array:
        """Stage X on device, padded to the tile grid and relabeled into the
        store's engine column space (optimized stores persist an operand
        permutation; raw stores pass through).  Skips the rebuild, copy,
        permute, and h2d accounting when ``x`` is already a padded float32
        array on this executor's device (the sharded path permutes once
        and stages once per device)."""
        with span("prepare_x", bytes=4 * self.padded_cols * x.shape[1]):
            already_dev = isinstance(x, jax.Array)
            if already_dev and x.shape[0] == self.padded_cols \
                    and x.dtype == jnp.float32:
                x_pad = x
                staged = False
            else:
                full = np.zeros((self.padded_cols, x.shape[1]), np.float32)
                full[: x.shape[0]] = np.asarray(x, np.float32)
                x_pad = jnp.asarray(self.store.apply_col_perm(full))
                staged = True
            if self.device is not None and x_pad.devices() != {self.device}:
                x_pad = jax.device_put(x_pad, self.device)
                staged = True
            if staged:
                self.store.stats.add_h2d(x_pad.nbytes)
            return x_pad

    def _lane_pad(self, p: int) -> int:
        """Extra dense columns needed to lane-align the Pallas operand:
        the compiled TPU target wants the block width to be a multiple of
        the 128-lane register width, while the interpreter (and the scan
        step) accept any p — :func:`repro.kernels.ops.lane_multiple` makes
        the same backend-driven choice as the kernel.  Applied on device,
        once per pass — the padding columns are zeros, contribute zeros,
        and are sliced off before the result leaves the engine, so they are
        invisible to callers (and to ``IOStats``: nothing extra crosses the
        host->device boundary)."""
        if not self.cfg.use_pallas:
            return 0
        from repro.kernels import ops
        return (-p) % ops.lane_multiple()

    def _pad_tail(self, batches: Iterator[Tuple[np.ndarray, ...]],
                  pow2: bool = False
                  ) -> Iterator[Tuple[Tuple[np.ndarray, ...], int]]:
        """Pad a short batch to a fixed shape so the jitted step compiles a
        bounded number of entries; yields ``(batch, n_valid)`` with the
        real chunk count.  A classic plan (one short batch: the tail) pads
        to ``chunk_batch`` — exactly one shape per pass.  A fragmented plan
        (an optimized store's encoding-run splits: many short batches,
        ``pow2=True``) instead pads short runs to the next power of two and
        mid-size runs (>= 32) to the next multiple of 32 — still a bounded
        shape count, but without inflating a 70-chunk run to 128 shipped-
        and-scanned chunks the way pure power-of-two rounding would.  Pad
        chunks
        replicate the last chunk's tile coordinates with nnz = 0 and zero
        entries — their contribution is identically zero, no
        first-of-tile-row flag is disturbed, and (the Pallas kernel's
        window invariant) they never open an output block the batch's real
        chunks did not."""
        B = self.cfg.chunk_batch
        for batch in batches:
            meta = batch[0]
            n = meta.shape[0]
            tgt = B
            if pow2 and 0 < n < B:
                if n < 32:
                    tgt = 1
                    while tgt < n:
                        tgt *= 2
                else:
                    tgt = min(-(-n // 32) * 32, B)
            if n == tgt or n == 0:
                yield batch, n
                continue
            meta_p = np.zeros((tgt, meta.shape[1]), meta.dtype)
            meta_p[:n] = meta
            meta_p[n:, 0] = meta[-1, 0]   # keep pointing at a live tile row:
            meta_p[n:, 1] = meta[-1, 1]   # a pad chunk must not re-init or
            meta_p[n:, 2] = 0             # mark-present a foreign block
            padded = [meta_p]
            for a in batch[1:]:
                if a is None:
                    padded.append(None)
                    continue
                a_p = np.zeros((tgt,) + a.shape[1:], a.dtype)
                a_p[:n] = a
                padded.append(a_p)
            yield tuple(padded), n

    @staticmethod
    def _with_valid(batches: Iterator[Tuple[np.ndarray, ...]]
                    ) -> Iterator[Tuple[Tuple[np.ndarray, ...], int]]:
        """No tail padding: every chunk of every batch is valid."""
        for batch in batches:
            yield batch, batch[0].shape[0]

    def _stage(self, batch: Tuple[np.ndarray, ...], n_valid: int) -> tuple:
        """Issue the host->device transfer for one batch (async — returns
        immediately; overlapped with the in-flight kernel when the engine
        runs a batch ahead).  Counts the actual bytes shipped: uint16
        indices cost half the decoded int32, binary matrices ship no
        values.  ``meta`` is staged like every other plane on every path;
        the Pallas step additionally ships the batch's valid-chunk count
        (one int32 — its 4 bytes are counted too, so ``IOStats.h2d_bytes``
        stays equal to what actually crossed to the device)."""
        with span("stage"):
            meta, rest = batch[0], batch[1:]
            dev_rest = tuple(None if a is None
                             else jax.device_put(a, self.device)
                             for a in rest)
            dev_meta = jax.device_put(meta, self.device)
            if self.cfg.use_pallas:
                nv = jax.device_put(np.asarray([n_valid], np.int32),
                                    self.device)
                staged = (dev_meta, nv) + dev_rest
            else:
                staged = (dev_meta,) + dev_rest
            self.store.stats.add_h2d(
                sum(a.nbytes for a in staged if a is not None))
            return staged

    def _make_step(self, binary_raw: bool, ring: Semiring = PLUS_TIMES):
        """Bind the kernel for this pass: Pallas wave kernel (gather or MXU
        variant, ``pick_variant`` by default), binary raw step (no values),
        or the general scan step.  ``x_pad`` is threaded through per call
        (a boundary hook may swap in a same-shape update mid-pass without
        touching the jit entry).  Every path consumes only staged device
        arrays — the Pallas step recomputes first-flags in-kernel, so no
        host meta survives past :meth:`_stage`.  Non-plus-times rings take
        the explicitly-masked scan step on every backend (the Pallas MXU
        kernel is plus-times only); the Pallas staging layout (with its
        extra ``n_valid`` scalar) is preserved so the pass plumbing does
        not fork."""
        if not ring.is_plus_times():
            strip_nv = self.cfg.use_pallas

            def step(staged, x_pad, out):
                if strip_nv:
                    meta, _nv, rows, cols, vals = staged
                else:
                    meta, rows, cols, vals = staged
                return _batch_step_ring(meta, rows, cols, vals, x_pad, out,
                                        self.T, ring.name)
            return step
        if self.cfg.use_pallas:
            from repro.kernels.ops import pick_variant, spmm_pallas_batch
            variant = self.cfg.pallas_variant or pick_variant(self.T)

            def step(staged, x_pad, out):
                meta, nv, rows, cols, vals = staged
                return spmm_pallas_batch(meta, nv, rows, cols, vals,
                                         x_pad, out, T=self.T,
                                         variant=variant)
        elif binary_raw:
            def step(staged, x_pad, out):
                meta, rows, cols, _ = staged
                return _batch_step_binary(meta, rows, cols, x_pad, out,
                                          self.T)
        else:
            def step(staged, x_pad, out):
                meta, rows, cols, vals = staged
                return _batch_step(meta, rows, cols, vals, x_pad, out, self.T)
        return step

    def _boundary(self, hook, chunk_start: int, x_pad: jax.Array,
                  out: jax.Array) -> jax.Array:
        """Run the boundary hook (if any) before a batch is dispatched;
        returns the possibly-updated operand."""
        if hook is None:
            return x_pad
        with span("boundary"):
            b = PassBoundary(self, chunk_start, x_pad, out)
            hook(b)
            return b.x_pad

    @staticmethod
    def _timed_reads(batches: Iterator) -> Iterator:
        """``batches`` with each ``next`` under a ``read_wait`` span: the
        time the dispatch thread waits for the store's next batch."""
        while True:
            with span("read_wait"):
                item = next(batches, None)
            if item is None:
                return
            yield item

    # -- the delta overlay ---------------------------------------------------
    def _chunk_trow(self) -> np.ndarray:
        """chunk_tile_rows(), cached per (generation, n_chunks) — a
        compaction install rewrites the layout under the same path."""
        key = (self.store.generation, self.store.n_chunks)
        if self._trow_key != key:
            self._trow_cache = self.store.chunk_tile_rows()
            self._trow_key = key
        return self._trow_cache

    # The staged delta snapshot is padded to this floor (doubling beyond
    # it), so the jitted delta scatter sees ONE shape for any log up to 8K
    # entries — churny serving must not retrace as the log grows, or the
    # per-pass overhead is compile time, not scatter time.  96 KB of H2D
    # per pass at the floor: noise next to a chunk batch.
    DELTA_PAD_FLOOR = 8192

    def _stage_delta(self, rows: np.ndarray, cols: np.ndarray,
                     vals: np.ndarray) -> tuple:
        """Ship the pass's whole (frame-sliced) delta snapshot as one
        staged buffer, length-padded to the fixed floor (then powers of
        two): the jitted shape set does not grow with the log, and staging
        costs three transfers per pass, not three per batch."""
        n = rows.shape[0]
        tgt = self.DELTA_PAD_FLOOR
        while tgt < n:
            tgt *= 2
        rp = np.zeros(tgt, np.int32)
        cp = np.zeros(tgt, np.int32)
        vp = np.zeros(tgt, np.float32)
        rp[:n], cp[:n], vp[:n] = rows, cols, vals
        dr = jax.device_put(rp, self.device)
        dc = jax.device_put(cp, self.device)
        dv = jax.device_put(vp, self.device)
        self.store.stats.add_h2d(dr.nbytes + dc.nbytes + dv.nbytes)
        return (dr, dc, dv)

    def _prepare_delta(self, snap, starts, ring: Semiring):
        """Slice a pass-start delta snapshot to this executor's row frame
        and assign each tile row's entries to a chunk batch: tile row t's
        delta is applied immediately AFTER the batch containing t's first
        base chunk — by then the operand columns that batch's boundary
        admitted are staged (rows at/after an admission boundary have all
        their chunks at/after it), and any completion read at a later
        boundary already includes the delta (rows below a boundary have
        their first chunk, hence their delta batch, strictly before it).
        Returns ``(dr, dc, dv, nv, tr_lo, tr_hi)`` — the snapshot staged
        once as one device buffer, its valid-entry count, and per-batch
        tile-row windows ``[tr_lo[i], tr_hi[i])`` (contiguous and
        exhaustive: a tile row's first chunk is nondecreasing in the row,
        so each tile row folds in exactly one batch) — or None when the
        snapshot holds nothing for this frame."""
        ver, rows, cols, vals = snap
        if rows.size == 0:
            return None
        st = self.store
        if not ring.is_plus_times() and st.delta_log.has_deletes:
            raise ValueError(
                f"semiring {ring.name!r} cannot serve a delta log with "
                "deletions (negated values only cancel under plus-times); "
                "compact the graph first")
        r0 = st.row_offset
        lo, hi = np.searchsorted(rows, [r0, r0 + self.n_rows])
        if hi == lo:
            return None
        rows = (rows[lo:hi] - r0).astype(np.int32)
        cols = cols[lo:hi]
        vals = np.asarray(vals[lo:hi], np.float32)
        perm = st.col_perm()
        if perm is not None:
            rank = np.empty_like(perm)
            rank[perm] = np.arange(perm.shape[0], dtype=perm.dtype)
            cols = rank[cols]
        cols = cols.astype(np.int32)
        # first base chunk of every tile row (each tile row owns >= 1
        # chunk, even when empty), then the batch that chunk falls in
        first_chunk = np.searchsorted(self._chunk_trow(),
                                      np.arange(self.n_tile_rows))
        sarr = np.asarray(starts, np.int64)
        batch_of_row = np.clip(
            np.searchsorted(sarr, first_chunk, side="right") - 1,
            0, len(starts) - 1)
        b = np.arange(len(starts))
        tr_lo = np.searchsorted(batch_of_row, b, side="left").astype(np.int32)
        tr_hi = np.searchsorted(batch_of_row, b,
                                side="right").astype(np.int32)
        return self._stage_delta(rows, cols, vals) + (
            np.int32(rows.shape[0]), tr_lo, tr_hi)

    def _make_step_delta(self, step, binary_raw: bool, ring: Semiring,
                         delta_plan):
        """Bind one pass's delta-fused dispatch: ``dispatch(i, staged,
        x_pad, out)`` applies batch ``i`` AND folds its tile-row window of
        the pass-level delta accumulator in a single kernel launch —
        churny serving costs one dispatch per batch, same as frozen, plus
        ONE scatter per pass to build the accumulator.  The accumulator is
        bound to the operand staging: a mid-pass ``write_columns`` swaps
        ``x_pad`` (shape-preserving, new object), so the next dispatch
        rebuilds it and the not-yet-folded tile rows' delta re-gathers
        against the rewritten columns — exactly the columns their base
        chunks see.  The Pallas wave kernel cannot absorb the fold, so
        that path chases with :func:`_delta_fold`, skipping empty
        windows."""
        dr, dc, dv, nv, tr_lo, tr_hi = delta_plan
        T, ntr = self.T, self.n_tile_rows
        state = {"src": None, "dacc": None}

        def dacc_for(x_pad):
            if state["src"] is not x_pad:
                state["dacc"] = (
                    _delta_acc(dr, dc, dv, nv, x_pad, ntr, T)
                    if ring.is_plus_times() else
                    _delta_acc_ring(dr, dc, dv, nv, x_pad, ntr, T,
                                    ring.name))
                state["src"] = x_pad
            return state["dacc"]

        if not ring.is_plus_times():
            strip_nv = self.cfg.use_pallas

            def dispatch(i, staged, x_pad, out):
                if strip_nv:
                    meta, _nv, rows, cols, vals = staged
                else:
                    meta, rows, cols, vals = staged
                return _batch_step_ring_delta(
                    meta, rows, cols, vals, dacc_for(x_pad), tr_lo[i],
                    tr_hi[i], x_pad, out, T, ring.name)
            return dispatch
        if self.cfg.use_pallas:
            def dispatch(i, staged, x_pad, out):
                out = step(staged, x_pad, out)
                if tr_hi[i] > tr_lo[i]:
                    out = _delta_fold(out, dacc_for(x_pad), tr_lo[i],
                                      tr_hi[i])
                return out
            return dispatch
        if binary_raw:
            def dispatch(i, staged, x_pad, out):
                meta, rows, cols, _ = staged
                return _batch_step_binary_delta(
                    meta, rows, cols, dacc_for(x_pad), tr_lo[i], tr_hi[i],
                    x_pad, out, T)
            return dispatch

        def dispatch(i, staged, x_pad, out):
            meta, rows, cols, vals = staged
            return _batch_step_delta(
                meta, rows, cols, vals, dacc_for(x_pad), tr_lo[i], tr_hi[i],
                x_pad, out, T)
        return dispatch

    def _stream_pass(self, x_pad: jax.Array, out: jax.Array,
                     hook=None, cache=_CACHE_UNSET,
                     ring: Semiring = PLUS_TIMES,
                     snapshot=None) -> jax.Array:
        """One full streaming pass of the sparse matrix, accumulated into the
        donated ``out`` blocks.  ``cache`` overrides the executor-attached
        hot-chunk cache for this pass only (the fleet's waves share one
        executor but each reads through its own budget slice).  When the
        store carries a delta log, the log is snapshotted once at pass
        start (bracketed by ``begin_pass``/``end_pass`` so a compaction
        cannot install a new base generation mid-stream) and each batch's
        base step is chased by the delta contribution for the tile rows it
        completed — the pass computes ``(base ⊕ delta) @ X`` at one
        consistent version."""
        raw = self._use_raw()
        pass_cache = self.cache if cache is _CACHE_UNSET else cache
        handle = self.store.handle
        dl = self.store.delta_log
        snap = None
        if dl is not None:
            if handle is not None:
                # begin_pass gates installation AND returns the current
                # snapshot; a caller coordinating several executors (the
                # sharded scan) supplies one shared snapshot instead so
                # every partial scan serves exactly one version.
                got = handle.begin_pass()
                snap = snapshot if snapshot is not None else got
            else:
                snap = snapshot if snapshot is not None else dl.snapshot()
            self.last_pass_version = snap[0]
        try:
            batches = (iter(self._cached) if self._cached is not None else
                       self.store.stream(self.cfg.chunk_batch,
                                         prefetch=self.cfg.prefetch,
                                         use_async=self.cfg.use_async,
                                         cache=pass_cache, raw=raw))
            binary_raw = raw and self.store.header["binary"]
            step = self._make_step(binary_raw, ring)
            stats = self.store.stats
            B = self.cfg.chunk_batch
            # Batch boundaries come from the store's plan, not ``i * B``: an
            # optimized store splits batches at encoding-run boundaries, so
            # the i-th batch does not start at chunk i*B in general.
            starts = [s for s, _ in self.store.batch_plan(B)]
            fragmented = len(starts) > -(-self.store.n_chunks // B)
            delta_plan = (self._prepare_delta(snap, starts, ring)
                          if snap is not None else None)
            if delta_plan is None:
                def dispatch(i, staged, x_pad, out):
                    return step(staged, x_pad, out)
            else:
                dispatch = self._make_step_delta(step, binary_raw, ring,
                                                 delta_plan)
            batches = self._timed_reads(
                self._pad_tail(batches, pow2=fragmented)
                if self.cfg.fixed_shape else self._with_valid(batches))
            with span("stream"):
                if not self.cfg.overlap:
                    for i, (batch, nv) in enumerate(batches):
                        x_pad = self._boundary(hook, starts[i], x_pad, out)
                        staged = self._stage(batch, nv)
                        with span("step"):
                            out = dispatch(i, staged, x_pad, out)
                else:
                    pending = None
                    for i, (batch, nv) in enumerate(batches):
                        staged = self._stage(batch, nv)  # stage k+1 ...
                        if pending is not None:
                            j, st_j = pending
                            x_pad = self._boundary(hook, starts[j], x_pad,
                                                   out)
                            with span("step"):  # ... while k runs
                                out = dispatch(j, st_j, x_pad, out)
                            stats.add_overlap()
                        pending = (i, staged)
                    if pending is not None:
                        j, st_j = pending
                        x_pad = self._boundary(hook, starts[j], x_pad, out)
                        with span("step"):
                            out = dispatch(j, st_j, x_pad, out)
        finally:
            if handle is not None and snap is not None:
                handle.end_pass()
        with self._passes_lock:
            self.passes += 1
        return out

    # -- regime 1/2: X in memory ------------------------------------------
    def multiply(self, x: np.ndarray, *, boundary_hook=None,
                 cache=_CACHE_UNSET,
                 semiring: str = "plus_times", snapshot=None) -> np.ndarray:
        """A @ X with X (n, p) in memory; returns in-memory result.
        ``boundary_hook`` (optional) is called with a :class:`PassBoundary`
        before each chunk batch — the elastic-admission entry point.
        ``cache`` (optional) overrides the attached hot-chunk cache for this
        pass — how concurrent serving waves sharing one executor each read
        through their own arbitrated budget slice (``None`` = uncached).
        ``semiring`` names a ring from :mod:`repro.core.semiring` —
        ``min_plus`` turns the pass into one shortest-path relaxation.
        ``snapshot`` (optional) supplies a pre-taken delta snapshot so a
        coordinator fanning one logical pass across several executors can
        hold every partial scan at one version."""
        out, _ = self._multiply(x, boundary_hook=boundary_hook, cache=cache,
                                semiring=semiring, snapshot=snapshot)
        return out

    def _multiply(self, x: np.ndarray, acc: Optional[jax.Array] = None,
                  boundary_hook=None, cache=_CACHE_UNSET,
                  semiring: str = "plus_times", snapshot=None
                  ) -> Tuple[np.ndarray, Optional[jax.Array]]:
        """multiply() plus accumulator reuse: a caller looping over slices of
        equal width passes back the returned ``acc`` (still holding the
        previous slice's blocks — it is re-zeroed in place here, via
        donation, only when actually reused; a one-shot multiply() never
        pays the zero-fill)."""
        ring = (semiring if isinstance(semiring, Semiring)
                else SEMIRINGS[semiring])
        p = x.shape[1]
        x_pad = self._prepare_x(x)
        pw = p + self._lane_pad(p)
        if pw != p:
            x_pad = jnp.pad(x_pad, ((0, 0), (0, pw - p)),
                            constant_values=0.0)
        if acc is None or acc.shape[2] != pw:
            acc = jnp.full((self.n_tile_rows, self.T, pw),
                           jnp.float32(ring.zero), jnp.float32,
                           device=self.device)
        elif ring.is_plus_times():
            acc = _zero_acc(acc)
        else:
            acc = _fill_acc(acc, float(ring.zero))
        out = self._stream_pass(x_pad, acc, hook=boundary_hook, cache=cache,
                                ring=ring, snapshot=snapshot)
        with span("sync"):
            out.block_until_ready()   # only here — never inside the pass
        with span("copyback", bytes=4 * self.n_rows * p):
            result = np.asarray(out.reshape(-1, pw)[: self.n_rows, :p])
        self.store.stats.add_d2h(result.nbytes)
        return result, out

    # -- regime 3: vertical partitioning ------------------------------------
    def column_bytes(self) -> int:
        """Memory cost of one dense column (input slice + output slice)."""
        return 4 * (self.n_rows + self.padded_cols)

    def stream_overhead_bytes(self) -> int:
        """Memory cost of the streaming buffers (one in-flight chunk batch
        per prefetch slot plus the one being consumed)."""
        return self.store.header["record"] * self.cfg.chunk_batch * (
            self.cfg.prefetch + 1)

    def columns_that_fit(self, p_total: int) -> int:
        """How many dense columns fit the memory budget (input slice +
        output slice + one chunk batch of buffers), min 1 (paper: minimum
        memory requirement is O(n) — one column)."""
        fit = (self.cfg.memory_budget_bytes - self.stream_overhead_bytes()
               ) // self.column_bytes()
        return int(max(1, min(p_total, fit)))

    def leftover_budget(self, cols_in_use: int) -> int:
        """Memory budget remaining after ``cols_in_use`` dense columns and
        the streaming buffers are paid for — what the serving runtime may
        spend on pinning hot chunk batches (§3.6 inverted: once every dense
        column is resident, the next-best use of a byte IS the sparse
        matrix)."""
        return max(0, self.cfg.memory_budget_bytes
                   - self.stream_overhead_bytes()
                   - self.column_bytes() * cols_in_use)

    def multiply_external(self, x_store: DenseStore, out_store: DenseStore,
                          cols_in_memory: Optional[int] = None) -> IOStats:
        """A @ X with X on the slow tier: vertical partitioning.  Each slice
        triggers one full streaming pass over the sparse matrix (paper
        §3.6: passes = ceil(p / p_fit)); the output accumulator is donated
        back and reused across equal-width slices."""
        p_total = x_store.n_cols
        p_fit = cols_in_memory or self.columns_that_fit(p_total)
        acc = None
        for c0 in range(0, p_total, p_fit):
            c1 = min(c0 + p_fit, p_total)
            x_slice = x_store.read_cols(c0, c1)      # slow tier -> memory
            out_slice, acc = self._multiply(x_slice, acc)  # stream A
            out_store.write_cols(c0, out_slice)      # write-once
        out_store.flush()
        return out_store.stats

    @property
    def n_batches(self) -> int:
        """Chunk batches per streaming pass (boundary-hook call count) —
        the store's batch plan, which splits at encoding-run boundaries on
        optimized stores."""
        return len(self.store.batch_plan(self.cfg.chunk_batch))

    @property
    def io_stats(self) -> IOStats:
        return self.store.stats

    def close(self) -> None:
        """Release the store's file mappings (and the IM-mode resident
        batches).  Idempotent — the Executor protocol requires close() to
        be safe from both an exception path and a normal exit."""
        self._cached = None
        self.store.close()

    def __enter__(self) -> "SEMSpMM":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
