"""Sharded parallel scans: row-partition one TileStore, stream every shard
at once.

The paper scales SEM-SpMM on one box by balancing tile rows across worker
threads behind a shared I/O stream; here the *store itself* is partitioned
(:meth:`TileStore.partition_rows`) into contiguous tile-row shards over the
same backing file, and each shard runs its own complete streaming pass —
its own prefetch thread, its own stats, its own (optionally per-device)
compute.  That is the BigSparse/SSD-eigensolver scaling shape: parallel
partial scans plus a row-block concatenation, with no cross-shard
communication because the row partition makes output blocks disjoint.

Because every chunk of a tile row lives in exactly one shard and shards
preserve chunk order, each output row accumulates its contributions in
exactly the order the single-scan engine uses — the concatenated result is
bit-identical, not merely allclose.

With one JAX device, shards run on threads: the prefetch threads overlap
each other's page faults and the per-shard passes release the GIL inside
XLA compute.  With multiple JAX devices (a four-chip TPU host) each
shard's operand and accumulator are pinned round-robin via
``SEMSpMM(device=...)``, turning the same code into a one-device-per-shard
parallel scan; the shared operand is copied from the host to each device
once per pass.

Two scaling knobs compose here: ``replicas=`` spreads the shards of one
wave across N copies of the matrix (per-SSD/per-NUMA paths — each shard
streams a different spindle), and a partitioned hot-chunk cache
(``cache.shard(i)``) gives every shard its own pin budget so a fast shard
cannot evict a slow shard's hot batches.

The per-shard compute step is whatever the shared :class:`SEMConfig`
selects — including ``use_pallas=True``, where every shard drives its own
Pallas wave kernel over its rebased tile rows (the shard's meta is already
in shard-frame coordinates, so the kernel's accumulator covers exactly the
shard's row blocks); the concatenated result stays bit-identical to the
single-scan Pallas pass.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import List, Optional, Sequence

import jax
import numpy as np

import threading

from repro.core.sem import _CACHE_UNSET, SEMConfig, SEMSpMM
from repro.io.storage import (GraphHandle, IOStats, TileStore, UpdateBatch,
                              validate_replicas)


class _RecordingBoundary:
    """Proxy around the coordinator shard's :class:`PassBoundary` that logs
    every ``write_columns`` call so :meth:`ShardedSEMSpMM.multiply` can
    replay the same writes onto the operand the held-back shards stream.
    ``read_output``/``chunk_start`` pass straight through — the coordinator
    shard starts at global chunk 0, so both are already in global frame."""

    def __init__(self, inner, writes):
        self._inner = inner
        self._writes = writes

    @property
    def chunk_start(self):
        return self._inner.chunk_start

    def read_output(self, n_tile_rows: int, c0: int, c1: int) -> np.ndarray:
        # n_tile_rows is bounded by the coordinator's own tile rows (every
        # boundary's chunk_start lies inside shard 0's chunk space), and
        # with >= 2 shards the coordinator's row count is an exact multiple
        # of T, so the inner clamp is a no-op — the read is global-exact.
        return self._inner.read_output(n_tile_rows, c0, c1)

    def write_columns(self, c0: int, cols: np.ndarray) -> None:
        cols = np.asarray(cols, np.float32)
        if cols.ndim == 1:
            cols = cols[:, None]
        self._writes.append((c0, cols))
        self._inner.write_columns(c0, cols)


class ShardedSEMSpMM:
    """Parallel sharded scans over row-partitioned :class:`TileStore` shards.

    Duck-types the slice of :class:`SEMSpMM` the serving scheduler consumes
    (``multiply``, ``passes``, ``io_stats``) so a wave's pass can fan out
    across shards behind the scheduler's ``sharded=`` knob.
    """

    def __init__(self, store: TileStore, n_shards: Optional[int] = None,
                 config: Optional[SEMConfig] = None, cache=None,
                 devices: Optional[Sequence] = None,
                 replicas: Optional[Sequence[TileStore]] = None):
        if devices is None:
            devs = jax.devices()
            devices = devs if len(devs) > 1 else None
        if n_shards is None:
            n_shards = len(devices) if devices else 2
        self.store = store
        self.cfg = config or SEMConfig()
        # Replica-aware shard placement: with N copies of the matrix (same
        # logical bytes, different spindles/paths), shard i streams from
        # copy i mod N — the shards of ONE wave fan out across replicas and
        # scan bandwidth scales with spindles instead of being fixed per
        # store.  Every source is partitioned identically (the split is a
        # pure function of the shared header + meta), so shard i covers the
        # same tile rows regardless of which copy serves it.
        sources = [store]
        if replicas:
            validate_replicas([store] + list(replicas))
            sources = [store] + list(replicas)
        per_source = [s.partition_rows(n_shards) for s in sources]
        n_shards = len(per_source[0])  # partition_rows may clamp
        self.shards = [per_source[i % len(sources)][i]
                       for i in range(n_shards)]
        # The shard views hold layout state derived from the current base
        # generation (chunk ranges, tags, offsets) — pin it so a compaction
        # cannot install a new generation under them.  Pins are taken on
        # every source's handle (lazily, if mutation starts after
        # construction) and dropped in close().
        self._sources = sources
        self._mut_lock = threading.Lock()
        self._pinned: List[GraphHandle] = []
        for s in sources:
            if s.handle is not None and s.handle not in self._pinned:
                s.handle.pin_layout()
                self._pinned.append(s.handle)
        self.execs: List[SEMSpMM] = [
            SEMSpMM(s, self.cfg,
                    cache=cache.shard(i) if hasattr(cache, "shard")
                    else cache,
                    device=devices[i % len(devices)] if devices else None)
            for i, s in enumerate(self.shards)]
        h = store.header
        self.n_rows, self.n_cols, self.T = h["n_rows"], h["n_cols"], h["T"]
        self.padded_cols = self.execs[0].padded_cols
        self.mode = "sem"
        self.passes = 0
        self.last_pass_version = 0
        self._pool = ThreadPoolExecutor(max_workers=len(self.execs),
                                        thread_name_prefix="shard-scan")

    @property
    def n_shards(self) -> int:
        return len(self.execs)

    # -- mutation surface (the Mutable protocol) ----------------------------
    @property
    def version(self) -> int:
        return self.store.version

    @property
    def delta_nnz(self) -> int:
        dl = self.store.delta_log
        return 0 if dl is None else dl.nnz

    @property
    def graph_handle(self) -> Optional[GraphHandle]:
        return self.store.handle

    def pin_layout(self) -> None:
        """Pin every source handle's layout (idempotent): the shard views'
        chunk ranges are derived from the current base generation, so a
        compaction install under a live sharded engine would dangle them.
        Called lazily — at construction, on first mutation, and by the
        scheduler when a handle appears after this engine was built."""
        with self._mut_lock:
            for s in self._sources:
                h = s.handle
                if h is not None and h not in self._pinned:
                    h.pin_layout()
                    self._pinned.append(h)

    def apply_updates(self, batch: UpdateBatch) -> int:
        """Append an edge-update batch to the graph's delta log; every
        shard's next pass snapshots it (the shard views delegate to the
        root store's log, and each slices the snapshot to its own row
        frame).  All replica sources share one handle — they are copies of
        the same logical bytes, so one log serves them all."""
        with self._mut_lock:
            if self.store.handle is None:
                GraphHandle(self._sources)
        self.pin_layout()
        return self.store.handle.apply_updates(batch)

    def multiply(self, x: np.ndarray, *, boundary_hook=None,
                 cache=_CACHE_UNSET,
                 semiring: str = "plus_times", snapshot=None) -> np.ndarray:
        """A @ X as ``n_shards`` partial scans; the per-shard row blocks
        concatenate (in partition order) to the full result.

        ``cache`` overrides each shard executor's attached hot-chunk cache
        for this pass (``None`` = uncached), the same per-pass arbitration
        knob :meth:`SEMSpMM.multiply` exposes.

        Without a ``boundary_hook`` every shard streams concurrently.  With
        one, the hook is threaded through the *coordinator shard* — shard
        0, whose chunk space is the global prefix ``[0, shard0_chunks)`` and
        whose tile rows are the lowest — and the remaining shards are held
        until the coordinator's scan completes, then run concurrently
        against the final (possibly hook-rewritten) operand.  That ordering
        is what makes mid-pass column writes compose bit-identically with
        the unsharded elastic pass: a column written at coordinator
        boundary ``cs`` reaches (a) coordinator tile rows at or after
        ``tr_start`` exactly as the single scan would, and (b) every
        non-coordinator tile row in full, because none of their chunks had
        streamed yet — the same set of rows the unsharded stitch credits.
        The cost is that the coordinator's scan is serialized ahead of the
        rest (an elastic sharded pass keeps mid-pass admission, not the
        full parallel-scan speedup; scale pure bandwidth with replicas).

        The hook's :class:`~repro.core.sem.PassBoundary` is the
        coordinator executor's: ``chunk_start`` is already global (shard 0
        starts at chunk 0), ``read_output`` covers the coordinator's
        completed tile-row prefix (every ``tr_start`` reachable from a
        coordinator boundary lies inside it), and ``write_columns`` is
        observed through a recording proxy so the writes can be replayed
        onto the operand the held-back shards stream against."""
        # Pad and relabel X once, then stage it once per device straight
        # from the host; every shard's ``_prepare_x`` then takes the
        # already-on-device skip path.
        x = np.asarray(x, np.float32)
        if x.shape[0] != self.padded_cols:
            x_pad = np.zeros((self.padded_cols, x.shape[1]), np.float32)
            x_pad[: x.shape[0]] = x
        else:
            x_pad = x
        # Relabel into an optimized store's engine column space once, for
        # all shards (no-op on raw stores).
        x_dev = self._stage_operand(self.store.apply_col_perm(x_pad))

        # One delta snapshot for the whole fan-out: shards stream
        # concurrently, and without a shared snapshot an update landing
        # mid-fan-out would leave row blocks at different versions inside
        # one result.  A caller-supplied snapshot pins it further up (the
        # scheduler shares one snapshot across a sliced wave's scans).
        snap = snapshot
        if snap is None:
            dl = self.store.delta_log
            snap = dl.snapshot() if dl is not None else None
        self.last_pass_version = snap[0] if snap is not None else 0

        # Per-pass cache override, shard-partitioned like the attached one
        # (a sharded cache hands each shard its own pin budget).
        def shard_cache(i):
            if cache is _CACHE_UNSET or not hasattr(cache, "shard"):
                return cache
            return cache.shard(i)

        if boundary_hook is None:
            blocks = list(self._pool.map(
                lambda iex: iex[1].multiply(x_dev[iex[1].device],
                                            cache=shard_cache(iex[0]),
                                            semiring=semiring, snapshot=snap),
                enumerate(self.execs)))
        else:
            writes: List[tuple] = []

            def recording_hook(b):
                boundary_hook(_RecordingBoundary(b, writes))

            head = self.execs[0].multiply(x_dev[self.execs[0].device],
                                          boundary_hook=recording_hook,
                                          cache=shard_cache(0),
                                          semiring=semiring, snapshot=snap)
            if writes:
                x_host = np.array(x_pad)   # replay in write order
                for c0, cols in writes:
                    x_host[: cols.shape[0], c0:c0 + cols.shape[1]] = cols
                    x_host[cols.shape[0]:, c0:c0 + cols.shape[1]] = 0.0
                # writes were recorded in user space; relabel the replayed
                # operand exactly like the initial staging above
                x_dev = self._stage_operand(
                    self.store.apply_col_perm(x_host))
            blocks = [head] + list(self._pool.map(
                lambda iex: iex[1].multiply(x_dev[iex[1].device],
                                            cache=shard_cache(iex[0]),
                                            semiring=semiring, snapshot=snap),
                enumerate(self.execs[1:], start=1)))
        self.passes += 1
        return np.concatenate(blocks, axis=0)

    def _stage_operand(self, x_host: np.ndarray) -> dict:
        """Copy the padded, relabeled operand from the host to every
        device the shards run on, once per device (one copy for all shards
        when they share the default device), counting each transfer."""
        staged = {}
        for ex in self.execs:
            if ex.device not in staged:
                staged[ex.device] = jax.device_put(x_host, ex.device)
                self.execs[0].store.stats.add_h2d(x_host.nbytes)
        return staged

    def column_bytes(self) -> int:
        """Memory cost of one dense column (input slice + output slice) —
        identical to the single-engine figure: shards share the operand and
        their output blocks partition the same n rows."""
        return 4 * (self.n_rows + self.padded_cols)

    # -- aggregated accounting (scheduler-facing) ----------------------------
    @property
    def io_stats(self) -> IOStats:
        """Point-in-time sum of the shard stores' counters."""
        return IOStats.aggregate(ex.store.stats for ex in self.execs)

    def close(self) -> None:
        """Release the scan thread pool and the shard views' file mappings
        (each shard holds its own memmap of the backing file; a serving run
        that never closed them leaked one mapping per shard per wave).
        Idempotent — safe from both an exception path and a normal exit."""
        self._pool.shutdown(wait=True)
        for h in self._pinned:
            h.unpin_layout()
        self._pinned = []
        for s in self.shards:
            s.close()

    def __enter__(self) -> "ShardedSEMSpMM":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
