"""Pallas kernel validation: shape/dtype sweeps + hypothesis properties vs
the ref.py oracle.  On the CPU backend the kernel runs in interpret mode
(the backend decides: ``repro.kernels.ops.use_interpreter``); its TPU
lowering is compiled in ``test_tpu_compile.py``."""
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.formats import COO, to_chunked
from repro.kernels.ops import pick_variant, spmm_pallas, spmm_pallas_batch
from repro.kernels.ref import spmm_ref
from repro.sparse.generate import rmat


def _ref(ct, x):
    x_pad = np.zeros((ct.padded_cols, x.shape[1]), np.float64)
    x_pad[: x.shape[0]] = x
    return spmm_ref(ct.meta, ct.row_local, ct.col_local, ct.vals, x_pad,
                    ct.T)[: ct.n_rows]


@pytest.mark.parametrize("variant", ["gather", "mxu"])
@pytest.mark.parametrize("T,C,p", [(128, 32, 1), (256, 64, 3), (256, 128, 8),
                                   (512, 128, 16),
                                   # tiles no multiple of the MXU's slab
                                   (768, 128, 8), (1000, 128, 8)])
def test_kernel_shape_sweep(small_valued, variant, T, C, p):
    ct = to_chunked(small_valued, T=T, C=C)
    rng = np.random.default_rng(p)
    x = rng.standard_normal((small_valued.n_cols, p)).astype(np.float32)
    out = np.asarray(spmm_pallas(ct, jnp.asarray(x), variant=variant))
    np.testing.assert_allclose(out, _ref(ct, x), atol=5e-4)


@pytest.mark.parametrize("variant", ["gather", "mxu"])
@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 5e-4),
                                        (jnp.bfloat16, 0.25)])
def test_kernel_dtype_sweep(small_valued, variant, dtype, atol):
    ct = to_chunked(small_valued, T=256, C=64)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((small_valued.n_cols, 4)).astype(np.float32)
    out = np.asarray(spmm_pallas(ct, jnp.asarray(x, dtype), variant=variant),
                     dtype=np.float64)
    ref = _ref(ct, x)
    np.testing.assert_allclose(out, ref, atol=atol, rtol=atol)


@settings(max_examples=20, deadline=None)
@given(n=st.integers(16, 300), nnz=st.integers(1, 2000),
       p=st.integers(1, 9), t=st.sampled_from([32, 128]),
       variant=st.sampled_from(["gather", "mxu"]),
       seed=st.integers(0, 2 ** 16))
def test_kernel_property(n, nnz, p, t, variant, seed):
    """Property: kernel == oracle for arbitrary random sparse matrices."""
    rng = np.random.default_rng(seed)
    coo = COO(n, n, rng.integers(0, n, nnz), rng.integers(0, n, nnz),
              None).dedup()
    coo = coo.with_values(rng.standard_normal(coo.nnz).astype(np.float32))
    ct = to_chunked(coo, T=t, C=16)
    x = rng.standard_normal((n, p)).astype(np.float32)
    out = np.asarray(spmm_pallas(ct, jnp.asarray(x), variant=variant))
    np.testing.assert_allclose(out, _ref(ct, x), atol=1e-3)


@pytest.mark.parametrize("variant", ["gather", "mxu"])
def test_batch_accumulation(small_valued, variant):
    """SEM streaming: applying chunk batches sequentially == one-shot.
    Batches start and end mid-tile-row, so this exercises the in-kernel
    first-flag recompute and the aliased-accumulator seeding."""
    ct = to_chunked(small_valued, T=256, C=64)
    rng = np.random.default_rng(1)
    x = rng.standard_normal((small_valued.n_cols, 3)).astype(np.float32)
    x_pad = jnp.zeros((ct.padded_cols, 3)).at[: x.shape[0]].set(x)
    out = jnp.zeros((ct.n_tile_rows, ct.T, 3))
    B = 7
    for s in range(0, ct.n_chunks, B):
        e = min(s + B, ct.n_chunks)
        out = spmm_pallas_batch(ct.meta[s:e], e - s, ct.row_local[s:e],
                                ct.col_local[s:e], ct.vals[s:e], x_pad, out,
                                T=ct.T, variant=variant)
    got = np.asarray(out.reshape(-1, 3)[: ct.n_rows])
    np.testing.assert_allclose(got, _ref(ct, x), atol=5e-4)


@pytest.mark.parametrize("variant", ["gather", "mxu"])
def test_wide_wave_column_blocks(small_valued, variant):
    """A wave wider than one lane width runs as one grid pass per 128-lane
    column block; batches that start and end mid-tile-row seed every
    block's output window from the accumulator."""
    ct = to_chunked(small_valued, T=256, C=64)
    p = 256
    rng = np.random.default_rng(4)
    x = rng.standard_normal((small_valued.n_cols, p)).astype(np.float32)
    x_pad = jnp.zeros((ct.padded_cols, p)).at[: x.shape[0]].set(x)
    out = jnp.zeros((ct.n_tile_rows, ct.T, p))
    B = 50
    for s in range(0, ct.n_chunks, B):
        e = min(s + B, ct.n_chunks)
        out = spmm_pallas_batch(ct.meta[s:e], e - s, ct.row_local[s:e],
                                ct.col_local[s:e], ct.vals[s:e], x_pad, out,
                                T=ct.T, variant=variant)
    got = np.asarray(out.reshape(-1, p)[: ct.n_rows])
    np.testing.assert_allclose(got, _ref(ct, x), atol=5e-4)


def test_batch_skips_tail_pads(small_valued):
    """Chunks past ``n_valid`` are skipped outright: poisoned pad planes
    (wild indices, NaN values, foreign meta rows) must not leak into the
    accumulator — the engine's fixed-shape tail relies on this."""
    ct = to_chunked(small_valued, T=256, C=64)
    rng = np.random.default_rng(2)
    x = rng.standard_normal((small_valued.n_cols, 3)).astype(np.float32)
    x_pad = jnp.zeros((ct.padded_cols, 3)).at[: x.shape[0]].set(x)
    want = spmm_pallas_batch(ct.meta, ct.n_chunks, ct.row_local,
                             ct.col_local, ct.vals, x_pad,
                             jnp.zeros((ct.n_tile_rows, ct.T, 3)), T=ct.T)
    pad = 5
    meta_p = np.concatenate([ct.meta, np.repeat(ct.meta[-1:], pad, 0)])
    meta_p[-pad:, 3] = 0
    rows_p = np.concatenate([ct.row_local,
                             np.full((pad, 64), 7, ct.row_local.dtype)])
    cols_p = np.concatenate([ct.col_local,
                             np.full((pad, 64), 7, ct.col_local.dtype)])
    vals_p = np.concatenate([ct.vals, np.full((pad, 64), np.nan, np.float32)])
    got = spmm_pallas_batch(meta_p, ct.n_chunks, rows_p, cols_p, vals_p,
                            x_pad, jnp.zeros((ct.n_tile_rows, ct.T, 3)),
                            T=ct.T)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_batch_preserves_untouched_tile_rows(small_valued):
    """Tile rows a batch never visits keep their accumulated content (the
    output aliases the accumulator; there is no present-mask to get wrong)."""
    ct = to_chunked(small_valued, T=256, C=64)
    rng = np.random.default_rng(3)
    x = rng.standard_normal((small_valued.n_cols, 3)).astype(np.float32)
    x_pad = jnp.zeros((ct.padded_cols, 3)).at[: x.shape[0]].set(x)
    acc0 = rng.standard_normal((ct.n_tile_rows, ct.T, 3)).astype(np.float32)
    # a mid-matrix batch: rows below/above its range must ride through
    s, e = ct.n_chunks // 3, 2 * ct.n_chunks // 3
    out = np.asarray(spmm_pallas_batch(
        ct.meta[s:e], e - s, ct.row_local[s:e], ct.col_local[s:e],
        ct.vals[s:e], x_pad, jnp.asarray(acc0), T=ct.T))
    touched = np.unique(ct.meta[s:e, 0])
    untouched = np.setdiff1d(np.arange(ct.n_tile_rows), touched)
    assert untouched.size > 0
    np.testing.assert_array_equal(out[untouched], acc0[untouched])
    assert not np.array_equal(out[touched], acc0[touched])


def test_variant_dispatch():
    assert pick_variant(512) == "mxu"
    assert pick_variant(2048) == "mxu"   # threshold is hardware-aligned
    assert pick_variant(16384) == "gather"  # the paper's tile size
    small_tiles = to_chunked(rmat(10, 2, seed=0), T=512, C=128)
    paper_tiles = to_chunked(rmat(10, 2, seed=0), T=16384, C=2048)
    assert pick_variant(small_tiles.T) == "mxu"
    assert pick_variant(paper_tiles.T) == "gather"
