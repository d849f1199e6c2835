"""The overlapped streaming engine: zero-copy uint16 reads, device-side
decode, overlapped staging, fixed-shape tail batches, sharded parallel
scans — all bit-exact against the ``spmm_chunked`` oracle — plus the
reader-thread failure path and the h2d/overlap accounting."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.formats import to_chunked
from repro.core.sem import SEMConfig, SEMSpMM
from repro.core.spmm import spmm_chunked
from repro.distributed.shard_scan import ShardedSEMSpMM
from repro.io.storage import DenseStore, TileStore
from repro.runtime import SharedScanScheduler

C = 128
T = 512
BATCH = 53  # does not divide the chunk count -> the tail batch is padded


@pytest.fixture(scope="module")
def ct(small_valued):
    return to_chunked(small_valued, T=T, C=C)


@pytest.fixture(scope="module")
def ct_bin(small_graph):
    return to_chunked(small_graph, T=T, C=C)


@pytest.fixture(scope="module")
def valued_path(ct, tmp_path_factory):
    path = str(tmp_path_factory.mktemp("engine") / "val")
    TileStore.write(path, ct)
    return path


@pytest.fixture(scope="module")
def binary_path(ct_bin, tmp_path_factory):
    path = str(tmp_path_factory.mktemp("engine") / "bin")
    TileStore.write(path, ct_bin, binary=True)
    return path


@pytest.fixture(scope="module")
def x8(small_valued):
    rng = np.random.default_rng(3)
    return rng.standard_normal((small_valued.n_cols, 8)).astype(np.float32)


def fresh(path, **cfg):
    return SEMSpMM(TileStore.open(path), SEMConfig(chunk_batch=BATCH, **cfg))


# -- bit-exactness -----------------------------------------------------------
def test_overlapped_engine_bit_exact_valued(ct, valued_path, x8):
    """Raw u16 + device decode + overlap + padded tail == the oracle, bit
    for bit (same per-element accumulation order)."""
    oracle = np.asarray(spmm_chunked(ct, jnp.asarray(x8)))
    y = fresh(valued_path).multiply(x8)
    np.testing.assert_array_equal(y, oracle)


def test_overlapped_engine_bit_exact_binary(ct_bin, binary_path, x8):
    """Binary store: values are synthesized on device, none streamed."""
    oracle = np.asarray(spmm_chunked(ct_bin, jnp.asarray(x8)))
    y = fresh(binary_path).multiply(x8)
    np.testing.assert_array_equal(y, oracle)


def test_engine_matches_serial_baseline(valued_path, x8):
    """The pipelined engine and the fully-serial decoded path agree bit for
    bit across every ablation axis."""
    serial = fresh(valued_path, decode_on_device=False, overlap=False,
                   fixed_shape=False, use_async=False).multiply(x8)
    for kw in (dict(),                      # everything on
               dict(overlap=False),
               dict(fixed_shape=False),
               dict(decode_on_device=False)):
        np.testing.assert_array_equal(fresh(valued_path, **kw).multiply(x8),
                                      serial)


def test_padded_tail_batch_compiles_once(valued_path, x8):
    """Fixed-shape batches: the tail is padded to chunk_batch, so one pass
    adds at most one (C, T, p) jit entry; without padding the tail shape
    adds a second."""
    from repro.core import sem as sem_mod
    x5 = x8[:, :5]  # a p no other test uses -> fresh jit-cache shapes
    sem = fresh(valued_path)
    assert sem.store.n_chunks % BATCH != 0  # the premise: a short tail
    before = sem_mod._batch_step._cache_size()
    sem.multiply(x5)
    assert sem_mod._batch_step._cache_size() - before == 1
    fresh(valued_path).multiply(x5)  # second pass: no new entries
    assert sem_mod._batch_step._cache_size() - before == 1
    fresh(valued_path, fixed_shape=False).multiply(x5)  # tail shape compiles
    assert sem_mod._batch_step._cache_size() - before == 2


def test_prepadded_x_skips_rebuild(ct, valued_path, x8):
    """An already-padded float32 operand is staged as-is (the sharded path
    relies on this to pad once for all shards)."""
    oracle = np.asarray(spmm_chunked(ct, jnp.asarray(x8)))
    x_pad = np.zeros((ct.padded_cols, x8.shape[1]), np.float32)
    x_pad[: x8.shape[0]] = x8
    np.testing.assert_array_equal(fresh(valued_path).multiply(x_pad), oracle)


def test_vertical_slices_reuse_accumulator(valued_path, small_valued, x8,
                                           tmp_path):
    """multiply_external's donated accumulator reuse is invisible in the
    results and the write-once discipline."""
    xs = DenseStore(str(tmp_path / "x.f32"), x8.shape[0], x8.shape[1])
    xs.write_cols(0, x8)
    out = DenseStore(str(tmp_path / "o.f32"), small_valued.n_rows, x8.shape[1])
    sem = fresh(valued_path)
    sem.multiply_external(xs, out, cols_in_memory=3)  # 8 cols -> 3+3+2 slices
    ref = small_valued.to_dense(np.float64) @ x8.astype(np.float64)
    np.testing.assert_allclose(out.to_array(), ref, atol=2e-4)
    assert out.stats.bytes_written == ref.size * 4
    assert sem.passes == 3


# -- reader-thread failure propagation ---------------------------------------
def test_reader_exception_propagates(valued_path):
    """A failed read inside the prefetch thread re-raises in the consumer
    instead of hanging it on a sentinel that never arrives."""
    store = TileStore.open(valued_path)
    calls = {"n": 0}
    real = store.read_batch_raw

    def flaky(start, count):
        calls["n"] += 1
        if calls["n"] == 2:
            raise OSError("injected read failure")
        return real(start, count)

    store.read_batch_raw = flaky
    consumed = 0
    with pytest.raises(OSError, match="injected read failure"):
        for _ in store.stream(BATCH, use_async=True, raw=True):
            consumed += 1
    assert consumed == 1  # first batch delivered, failure surfaced after


def test_reader_exception_propagates_through_multiply(valued_path, x8):
    sem = fresh(valued_path)

    def boom(start, count):
        raise OSError("disk died")

    sem.store.read_batch_raw = boom
    with pytest.raises(OSError, match="disk died"):
        sem.multiply(x8)


def test_abandoned_stream_releases_reader(valued_path):
    """The reverse failure direction: a consumer that abandons the iterator
    mid-pass must not leave the prefetch thread blocked forever on the
    bounded queue."""
    import threading
    store = TileStore.open(valued_path)
    n0 = threading.active_count()
    it = store.stream(1, prefetch=1, use_async=True, raw=True)
    next(it)   # reader is now ahead, blocked on the full queue
    it.close()  # generator finally joins the reader; must not hang
    assert threading.active_count() == n0


# -- IOStats accounting -------------------------------------------------------
def test_h2d_index_bytes_halved(valued_path, x8):
    """Device-side decode ships uint16 indices: exactly 2*2 bytes per lane
    saved vs the decoded int32 path, everything else equal."""
    u16 = fresh(valued_path)
    u16.multiply(x8)
    i32 = fresh(valued_path, decode_on_device=False)
    i32.multiply(x8)
    n_chunks = -(-u16.store.n_chunks // BATCH) * BATCH  # incl. tail padding
    saved = i32.store.stats.h2d_bytes - u16.store.stats.h2d_bytes
    assert saved == 4 * C * n_chunks      # index traffic exactly halved
    assert u16.store.stats.bytes_read == u16.store.nbytes  # same disk bytes


def test_h2d_binary_ships_no_values(binary_path, x8):
    """Binary matrices stage meta + u16 indices only: the value plane is
    synthesized on device."""
    sem = fresh(binary_path)
    sem.multiply(x8)
    n_chunks = -(-sem.store.n_chunks // BATCH) * BATCH
    x_pad_bytes = 4 * sem.padded_cols * x8.shape[1]
    expected = x_pad_bytes + n_chunks * (16 + 4 * C)  # meta + rows + cols
    assert sem.store.stats.h2d_bytes == expected


def test_overlap_batches_counted(valued_path, x8):
    """Every batch after the first overlaps its staging with the in-flight
    step; the serial path records none."""
    sem = fresh(valued_path)
    sem.multiply(x8)
    n_batches = -(-sem.store.n_chunks // BATCH)
    assert sem.store.stats.overlap_batches == n_batches - 1
    serial = fresh(valued_path, overlap=False)
    serial.multiply(x8)
    assert serial.store.stats.overlap_batches == 0


# -- the Pallas engine backend ------------------------------------------------
def pfresh(path, **cfg):
    """A Pallas-backed engine pinned to the gather variant — the one that is
    bit-identical to the ``_batch_step`` oracle (the MXU variant reassociates
    sums through its matmuls, so it gets allclose coverage instead)."""
    cfg.setdefault("pallas_variant", "gather")
    return fresh(path, use_pallas=True, **cfg)


def test_pallas_engine_bit_exact_valued(valued_path, x8):
    """use_pallas=True is a drop-in engine backend: same bits as the
    _batch_step engine (and hence the oracle) on the default pipeline —
    overlap + device decode + fixed-shape padded tail."""
    np.testing.assert_array_equal(pfresh(valued_path).multiply(x8),
                                  fresh(valued_path).multiply(x8))


def test_pallas_engine_feature_matrix(valued_path, x8):
    """Bit-identity holds across every engine ablation axis the PR 2/3
    stack serves through: overlap on/off, fixed-shape tail on/off, host
    decode, sync reads."""
    want = fresh(valued_path).multiply(x8)
    for kw in (dict(overlap=False), dict(fixed_shape=False),
               dict(decode_on_device=False), dict(use_async=False)):
        np.testing.assert_array_equal(pfresh(valued_path, **kw).multiply(x8),
                                      want, err_msg=repr(kw))


def test_pallas_engine_bit_exact_binary(binary_path, x8):
    """Binary raw path: the kernel synthesizes the lane mask from chunk nnz
    on device — no value plane is streamed, staged, or materialized."""
    np.testing.assert_array_equal(pfresh(binary_path).multiply(x8),
                                  fresh(binary_path).multiply(x8))


def test_pallas_padded_tail_leaves_foreign_rows_alone(valued_path, x8):
    """Regression (the padded-tail ``present`` bug): a short tail batch's
    pad chunks must not touch any tile row its real chunks do not — in
    particular not tile row 0, which the old host-side present-mask path
    could mark for every short tail.  The tail batch here covers only the
    store's last tile rows, so row 0's block must come out bit-identical."""
    sem = pfresh(valued_path)
    n, B = sem.store.n_chunks, BATCH
    tail_rows = np.unique(
        sem.store.chunk_tile_rows()[(n // B) * B:])
    assert n % B != 0 and 0 not in tail_rows  # the premise
    want = fresh(valued_path).multiply(x8)
    got = sem.multiply(x8)
    np.testing.assert_array_equal(got[: sem.T], want[: sem.T])
    np.testing.assert_array_equal(got, want)


def test_pallas_mxu_variant_allclose(valued_path, ct, x8):
    """The densify/MXU variant reassociates per-chunk sums through two
    matmuls — allclose, not bit-equal.  T=512 is also what pick_variant
    selects by default at this tile size."""
    from repro.kernels.ops import pick_variant
    assert pick_variant(T) == "mxu"
    oracle = np.asarray(spmm_chunked(ct, jnp.asarray(x8)))
    got = fresh(valued_path, use_pallas=True).multiply(x8)  # default variant
    np.testing.assert_allclose(got, oracle, atol=2e-4)


def test_pallas_h2d_accounting_parity(valued_path, binary_path, x8):
    """The Pallas path stages meta like any other plane (no uncounted
    ``jnp.asarray(meta)`` re-ship per step); the only delta vs the
    _batch_step engine is the 4-byte n_valid scalar per batch."""
    for path in (valued_path, binary_path):
        dense = fresh(path)
        dense.multiply(x8)
        pal = pfresh(path)
        pal.multiply(x8)
        n_batches = -(-dense.store.n_chunks // BATCH)
        assert (pal.store.stats.h2d_bytes
                == dense.store.stats.h2d_bytes + 4 * n_batches)
        # same disk traffic, same overlap behavior
        assert pal.store.stats.bytes_read == dense.store.stats.bytes_read
        assert (pal.store.stats.overlap_batches
                == dense.store.stats.overlap_batches == n_batches - 1)


def test_pallas_step_compiles_once_per_pass(valued_path, x8):
    """Fixed shapes + the traced n_valid scalar: a whole pass (padded tail
    included) adds exactly one jit entry for the Pallas step, and a second
    pass adds none."""
    from repro.kernels import ops as ops_mod
    x6 = x8[:, :6]  # a p no other test uses -> fresh jit-cache shapes
    before = ops_mod.spmm_pallas_batch._cache_size()
    pfresh(valued_path).multiply(x6)
    assert ops_mod.spmm_pallas_batch._cache_size() - before == 1
    pfresh(valued_path).multiply(x6)
    assert ops_mod.spmm_pallas_batch._cache_size() - before == 1


def test_pallas_boundary_hook_bit_identical(valued_path, x8):
    """A mid-pass column swap through PassBoundary lands identically on
    both engine backends: tile rows streamed after the boundary see the new
    column, rows before it the old one — bit for bit."""
    new_col = np.arange(x8.shape[0], dtype=np.float32) / x8.shape[0]
    results = {}
    for name, mk in (("dense", fresh), ("pallas", pfresh)):
        sem = mk(valued_path)
        seen = {"prefix": None}

        def hook(b, sem=sem, seen=seen):
            if b.chunk_start == 2 * BATCH:     # third boundary, mid-pass
                b.write_columns(3, new_col)
                seen["prefix"] = b.read_output(1, 0, 2)  # blocks, then reads
        results[name] = (sem.multiply(x8, boundary_hook=hook), seen["prefix"])
    np.testing.assert_array_equal(results["dense"][0], results["pallas"][0])
    np.testing.assert_array_equal(results["dense"][1], results["pallas"][1])
    # and the swap really took: column 3 differs from the no-hook pass
    assert not np.array_equal(results["pallas"][0][:, 3],
                              fresh(valued_path).multiply(x8)[:, 3])


def test_pallas_rejects_unknown_variant(valued_path, x8):
    """A typo'd pallas_variant must fail loudly, not silently fall through
    to the MXU path (whose float drift would masquerade as an engine bug)."""
    with pytest.raises(ValueError, match="unknown kernel variant"):
        fresh(valued_path, use_pallas=True,
              pallas_variant="vpu").multiply(x8)


def test_pallas_compiled_mode_lane_aligns_p(valued_path, monkeypatch):
    """On a TPU the kernel is compiled, and the compiled lowering requires
    the dense width to be a multiple of the 128 lane register width; the
    engine pads the operand/accumulator on device and slices the result
    back.  The backend decides (``ops.use_interpreter``); steering that
    decision here pins the alignment arithmetic the compiled kernel gets,
    without a program option (the compiled kernel itself cannot run on the
    CPU backend)."""
    from repro.kernels import ops
    from repro.kernels.ops import LANE
    # the CPU backend interprets the kernel, which (like the scan step)
    # accepts any width: nothing is padded
    assert ops.use_interpreter()
    assert pfresh(valued_path)._lane_pad(8) == 0
    assert fresh(valued_path)._lane_pad(8) == 0
    monkeypatch.setattr(ops, "use_interpreter", lambda: False)
    compiled = pfresh(valued_path)
    assert [compiled._lane_pad(p) for p in (1, 8, 128, 130)] \
        == [127, 120, 0, 126]
    assert all((p + compiled._lane_pad(p)) % LANE == 0 for p in range(1, 300))
    assert fresh(valued_path)._lane_pad(8) == 0   # the scan step never pads


def test_pallas_interpreter_is_chosen_by_backend(monkeypatch):
    """Interpret mode is decided in one place, from the backend: the
    interpreter on ``cpu``, the compiled kernel on ``tpu``, and an error
    (not a silent interpreter fallback) anywhere else."""
    import jax
    from repro.kernels import ops
    for backend, want in (("cpu", True), ("tpu", False)):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert ops.use_interpreter() is want
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="no Pallas path"):
        ops.use_interpreter()


def test_pallas_sharded_scan_bit_identical(valued_path, x8):
    """ShardedSEMSpMM drives the Pallas step per shard (rebased shard-frame
    meta, per-shard accumulator) and still concatenates to the single-scan
    bits."""
    single = fresh(valued_path).multiply(x8)
    cfg = SEMConfig(chunk_batch=BATCH, use_pallas=True,
                    pallas_variant="gather")
    with ShardedSEMSpMM(TileStore.open(valued_path), n_shards=2,
                        config=cfg) as sh:
        np.testing.assert_array_equal(sh.multiply(x8), single)
        assert sh.io_stats.bytes_read == sh.store.nbytes


def test_sharded_scan_boundary_hook_rides_coordinator(valued_path, x8):
    """The elastic hook rides shard 0 (the coordinator: its chunk space is
    the global prefix); a hook that only reads sees exactly shard 0's
    boundaries and the result stays bit-identical to the hookless scan."""
    clocks = []
    with ShardedSEMSpMM(TileStore.open(valued_path), n_shards=2,
                        config=SEMConfig(chunk_batch=BATCH)) as sh:
        plain = sh.multiply(x8)
        hooked = sh.multiply(
            x8, boundary_hook=lambda b: clocks.append(b.chunk_start))
    np.testing.assert_array_equal(hooked, plain)
    n_chunks = TileStore.open(valued_path).n_chunks
    assert clocks == sorted(clocks) and clocks
    assert all(0 <= c <= n_chunks for c in clocks)


# -- sharded parallel scans ---------------------------------------------------
@pytest.mark.parametrize("n_shards", [2, 4])
def test_sharded_scan_bit_identical(valued_path, x8, n_shards):
    single = fresh(valued_path).multiply(x8)
    with ShardedSEMSpMM(TileStore.open(valued_path), n_shards=n_shards,
                        config=SEMConfig(chunk_batch=BATCH)) as sh:
        np.testing.assert_array_equal(sh.multiply(x8), single)
        # each shard streamed its own disjoint byte range, exactly once
        assert sh.io_stats.bytes_read == sh.store.nbytes


def test_sharded_scan_binary_bit_identical(binary_path, x8):
    single = fresh(binary_path).multiply(x8)
    with ShardedSEMSpMM(TileStore.open(binary_path), n_shards=4,
                        config=SEMConfig(chunk_batch=BATCH)) as sh:
        np.testing.assert_array_equal(sh.multiply(x8), single)


def test_partition_rows_covers_store(valued_path):
    st = TileStore.open(valued_path)
    shards = st.partition_rows(4)
    assert sum(s.n_chunks for s in shards) == st.n_chunks
    assert sum(s.header["n_rows"] for s in shards) == st.header["n_rows"]
    offs = [s.chunk_offset for s in shards]
    assert offs == sorted(offs) and offs[0] == 0
    for s in shards:  # every shard's meta is rebased to its own block space
        meta, *_ = s.read_batch_raw(0, s.n_chunks)
        assert meta[:, 0].min() >= 0
        assert meta[:, 0].max() < -(-s.header["n_rows"] // s.header["T"])


def test_shared_cache_shard_and_whole_store(valued_path, x8):
    """One HotChunkCache serving both shard views and the whole store: a
    shard pins meta rebased to its own frame, so its keys must never hit an
    offset-0 reader's lookups (chunk_batch=1 makes every global chunk id a
    batch start in both views)."""
    from repro.runtime.cache import HotChunkCache
    cache = HotChunkCache(1 << 30)
    cfg = SEMConfig(chunk_batch=1)
    store = TileStore.open(valued_path)
    with ShardedSEMSpMM(store, n_shards=2, config=cfg, cache=cache) as sh:
        expect = sh.multiply(x8)  # populates shard-frame pins
        sem = SEMSpMM(TileStore.open(valued_path), cfg, cache=cache)
        np.testing.assert_array_equal(sem.multiply(x8), expect)
        # and the other direction: whole-store pins must not corrupt shards
        np.testing.assert_array_equal(sh.multiply(x8), expect)


def test_scheduler_sharded_wave(valued_path, x8):
    """A serving wave fans out across shards and returns the same columns
    as the dedicated single-scan multiply."""
    single = fresh(valued_path).multiply(x8)
    sem = fresh(valued_path)
    with SharedScanScheduler(sem, sharded=4) as sched:
        reqs = [sched.query(x8[:, i], tenant_id=str(i)) for i in range(8)]
        reports = sched.run()
        assert sum(r.scan_passes for r in reports) >= 1
        assert sum(r.bytes_read for r in reports) > 0
        for i, r in enumerate(reqs):
            np.testing.assert_array_equal(r.result, single[:, i])
