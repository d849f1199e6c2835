"""Ahead-of-time compiles of the main-path device programs for a TPU v5e
that is described, not attached: the engine's jitted scan steps and the
Pallas wave kernel in both variants, at the engine's real widths (T =
16384, C = 2048, 256-chunk batches, n = 2^22).  What the chip's compiler
would refuse — a block shape the lowering rejects, a program that does not
fit HBM — fails here at no chip time.  Nothing runs: a passing compile
says nothing about results or speed.

The topology is described inside a module fixture (never at import: only
one process at a time may load the TPU library), and the fixture skips the
module where it cannot be described."""
import jax
import jax.numpy as jnp
import pytest

T, C, B = 16384, 2048, 256
N = 1 << 22
NTR = N // T
HBM_BYTES = 16 * 10**9   # one v5e chip
LANE = 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an entry compiled for a described chip cannot be read back without
    # one: keep these compiles out of any persistent cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _shapes(sharding, p, *, packed=False, binary=False, n=N):
    """Abstract arguments of one engine step: (meta, rows, cols, vals,
    x_pad, out_blocks) at the real widths."""
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    plane = jnp.uint8 if packed else jnp.uint16
    return (s((B, 6 if packed else 4), jnp.int32), s((B, C), plane),
            s((B, C), plane), None if binary else s((B, C), jnp.float32),
            s((n, p), jnp.float32), s((n // T, T, p), jnp.float32))


def _check(compiled, *, kernel: bool):
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert total < HBM_BYTES, total
    assert ("tpu_custom_call" in compiled.as_text()) is kernel
    return ma


@pytest.mark.parametrize("p", [8, 128])
def test_scan_step_compiles(one_chip, p):
    from repro.core.sem import _batch_step
    meta, rows, cols, vals, x, out = _shapes(one_chip, p)
    ma = _check(_batch_step.lower(meta, rows, cols, vals, x, out, T)
                .compile(), kernel=False)
    if p == 8:
        # the (NTR, T, 8) accumulator's lanes are padded to 128 on the
        # chip: its temporaries are the whole padded accumulator
        assert ma.temp_size_in_bytes > N * 128 * 4


def test_binary_step_compiles(one_chip):
    from repro.core.sem import _batch_step_binary
    meta, rows, cols, _, x, out = _shapes(one_chip, 8, binary=True)
    _check(_batch_step_binary.lower(meta, rows, cols, x, out, T).compile(),
           kernel=False)


def test_ring_step_compiles(one_chip):
    from repro.core.sem import _batch_step_ring
    meta, rows, cols, vals, x, out = _shapes(one_chip, 8)
    _check(_batch_step_ring.lower(meta, rows, cols, vals, x, out, T,
                                  "min_plus").compile(), kernel=False)


def test_delta_step_compiles(one_chip):
    from repro.core.sem import _batch_step_delta
    meta, rows, cols, vals, x, out = _shapes(one_chip, 8, packed=True)
    tr = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    _check(_batch_step_delta.lower(meta, rows, cols, vals, out, tr, tr, x,
                                   out, T).compile(), kernel=False)


@pytest.mark.parametrize("variant,packed,binary", [
    ("gather", False, False), ("gather", True, True),
    ("mxu", False, False), ("mxu", True, True)])
def test_pallas_wave_kernel_compiles(one_chip, monkeypatch, variant, packed,
                                     binary):
    """The engine's Pallas step exactly as the chip runs it: compiled, with
    the dense width lane-padded to 128 (the CPU backend would pick the
    interpreter, so the test steers that one decision)."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "use_interpreter", lambda: False)
    meta, rows, cols, vals, x, out = _shapes(one_chip, ops.lane_multiple(),
                                             packed=packed, binary=binary)
    nv = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
    _check(ops.spmm_pallas_batch.lower(meta, nv, rows, cols, vals, x, out,
                                       T=T, variant=variant).compile(),
           kernel=True)


@pytest.mark.parametrize("variant", ["gather", "mxu"])
def test_pallas_wide_wave_compiles(one_chip, monkeypatch, variant):
    """A wave of 1024 columns (n = 2^20, so operand and accumulator fit
    HBM): the kernel walks 128-lane column blocks, so its VMEM need is that
    of one block, not of the whole width."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "use_interpreter", lambda: False)
    meta, rows, cols, vals, x, out = _shapes(one_chip, 1024, n=1 << 20)
    nv = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
    _check(ops.spmm_pallas_batch.lower(meta, nv, rows, cols, vals, x, out,
                                       T=T, variant=variant).compile(),
           kernel=True)


def test_pallas_gather_small_chunks_compile(one_chip, monkeypatch):
    """Chunks of 128 lanes: the gather variant's SMEM lane blocks are
    padded to the TPU's 1024-element tiling of a 1-D array, which a
    (128,) block of the flattened planes does not match."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "use_interpreter", lambda: False)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    n, t, c = 1 << 16, 512, 128
    args = (s((B, 4), jnp.int32), s((1,), jnp.int32), s((B, c), jnp.uint16),
            s((B, c), jnp.uint16), s((B, c), jnp.float32),
            s((n, LANE), jnp.float32), s((n // t, t, LANE), jnp.float32))
    _check(ops.spmm_pallas_batch.lower(*args, T=t, variant="gather")
           .compile(), kernel=True)
