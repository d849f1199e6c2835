"""The benchmark's graph against the program's generator and chunker."""
import numpy as np
import pytest

from bench import graph as G


class _FakeRng:
    """Stands in for numpy's generator inside ``rmat``: hands out the
    uniforms it was given, in order."""

    def __init__(self, draws):
        self.draws = list(draws)

    def random(self, n):
        out = self.draws.pop(0)
        assert out.shape == (n,)
        return out


def test_rmat_bits_match_the_program_generator(monkeypatch):
    from repro.sparse import generate

    scale, ef, a, b, c = 6, 4, 0.57, 0.19, 0.19
    m = ef << scale
    rng = np.random.default_rng(5)
    draws = [rng.random(m) for _ in range(2 * scale)]
    monkeypatch.setattr(generate.np.random, "default_rng",
                        lambda seed: _FakeRng(draws))
    want = generate.rmat(scale, ef, a=a, b=b, c=c, seed=0)
    rows = np.zeros(m, np.int64)
    cols = np.zeros(m, np.int64)
    for lvl in range(scale):
        rbit, cbit = G.rmat_bits(draws[2 * lvl], draws[2 * lvl + 1], a, b, c)
        rows = (rows << 1) | rbit
        cols = (cols << 1) | cbit
    keys = np.unique(rows * (1 << scale) + cols)
    assert np.array_equal(keys, want.rows * (1 << scale) + want.cols)


def test_rmat_device_statistics_match_the_program_generator():
    from repro.sparse.generate import rmat

    g = G.rmat_device(12, 16, 0.57, 0.19, 0.19, 2**40 + 3, 1024)
    ref = rmat(12, 16, seed=3)
    rows, cols = g.rows_cols()
    assert abs(g.nnz - ref.nnz) < 0.02 * ref.nnz
    half = 1 << 11
    for r, c in ((0, 0), (0, 1), (1, 0), (1, 1)):
        mine = np.mean(((rows >= half) == r) & ((cols >= half) == c))
        theirs = np.mean(((ref.rows >= half) == r) & ((ref.cols >= half) == c))
        assert abs(mine - theirs) < 0.01
    # unique edges, in (tile, row, col) order
    key = g.hi.astype(np.int64) * g.T + g.lo
    assert np.all(np.diff(key) > 0)


def test_rmat_device_is_deterministic_per_seed():
    a = G.rmat_device(10, 8, 0.57, 0.19, 0.19, 7, 256)
    b = G.rmat_device(10, 8, 0.57, 0.19, 0.19, 7, 256)
    c = G.rmat_device(10, 8, 0.57, 0.19, 0.19, 8, 256)
    assert np.array_equal(a.hi, b.hi) and np.array_equal(a.lo, b.lo)
    assert not (a.nnz == c.nnz and np.array_equal(a.hi, c.hi))


def test_graph500_graph_is_the_scrambled_symmetric_rmat_graph():
    """Scrambled and symmetrised, the graph is the directed one of the
    same seed relabelled by one permutation, each edge both ways, without
    self-loops: a simple undirected graph whose hubs no longer sit at the
    lowest ids."""
    import jax

    scale, T, seed = 10, 128, 2**35 + 9
    d = G.rmat_device(scale, 16, 0.57, 0.19, 0.19, seed, T)
    g = G.rmat_device(scale, 16, 0.57, 0.19, 0.19, seed, T, scramble=True,
                      symmetric=True)
    n = 1 << scale
    perm = np.asarray(jax.random.permutation(
        jax.random.fold_in(G.seed_key(seed), scale), n))
    r, c = d.rows_cols()
    r, c = perm[r], perm[c]
    r, c = r[r != c], c[r != c]
    want = np.unique(np.concatenate([r * n + c, c * n + r]))
    rows, cols = g.rows_cols()
    got = rows * n + cols
    assert np.array_equal(np.sort(got), want)
    assert np.all(rows != cols)
    key = g.hi.astype(np.int64) * g.T + g.lo
    assert np.all(np.diff(key) > 0)               # unique, in tile order
    deg = np.bincount(rows, minlength=n)
    assert np.argmax(deg) == perm[0]              # vertex 0's hub, moved
    cfg = {"scale": scale, "edge_factor": 16, "a": 0.57, "b": 0.19,
           "c": 0.19, "scramble": True, "symmetric": True}
    f = G.from_config(cfg, seed, T)
    assert np.array_equal(f.hi, g.hi) and np.array_equal(f.lo, g.lo)


@pytest.mark.parametrize("scale,T,C,block", [
    (10, 128, 64, 1 << 23), (12, 1024, 256, 1 << 23), (9, 512, 32, 1 << 23),
    (10, 128, 64, 1000), (12, 1024, 256, 1), (11, 256, 128, 20000)])
def test_chunk_layout_matches_to_chunked(scale, T, C, block):
    """Also in blocks of tile rows far smaller than the graph: a block of
    one edge still takes a whole tile row."""
    from repro.core.formats import COO, to_chunked

    g = G.rmat_device(scale, 16, 0.57, 0.19, 0.19, scale, T)
    rows, cols = g.rows_cols()
    ct = to_chunked(COO(g.n, g.n, rows, cols, None), T=T, C=C)
    meta, rl, cl = G.chunk_layout(g, C, block_edges=block)
    assert np.array_equal(meta, ct.meta)
    assert np.array_equal(rl, ct.row_local)
    assert np.array_equal(cl, ct.col_local)


def test_chunk_layout_keeps_empty_tile_rows():
    from repro.core.formats import COO, to_chunked

    n, T, C = 64, 8, 4
    rows = np.array([0, 1, 1, 9, 40, 40, 41, 63])
    cols = np.array([5, 2, 60, 9, 0, 1, 33, 63])
    order = np.lexsort((cols, rows, (rows // T) * (n // T) + cols // T))
    rows, cols = rows[order], cols[order]
    tile = (rows // T) * (n // T) + cols // T
    g = G.Graph(n, T, (tile * T + rows % T).astype(np.uint32),
                (cols % T).astype(np.uint32))
    ct = to_chunked(COO(n, n, rows, cols, None), T=T, C=C)
    meta, rl, cl = G.chunk_layout(g, C)
    assert (meta[:, 3] == 0).sum() >= 4          # empty tile rows are kept
    assert np.array_equal(meta, ct.meta)
    assert np.array_equal(rl, ct.row_local)
    assert np.array_equal(cl, ct.col_local)


def _one_shot(scale, edge_factor, a, b, c, seed, T, scramble, symmetric):
    """The generator as one jitted sort of every edge on the device (the
    form ``rmat_device`` had while every key fit 32 bits): the edge list
    the bucketed generator has to reproduce bit for bit."""
    import jax
    import jax.numpy as jnp

    n = 1 << scale
    tpr = n // T
    n_edges = edge_factor * n
    log_t = T.bit_length() - 1

    @jax.jit
    def gen(key):
        def level(i, rc):
            rows, cols = rc
            u = jax.random.uniform(jax.random.fold_in(key, i), (2, n_edges),
                                   jnp.float32)
            rbit, cbit = G.rmat_bits(u[0], u[1], a, b, c, xp=jnp)
            return ((rows << 1) | rbit.astype(jnp.uint32),
                    (cols << 1) | cbit.astype(jnp.uint32))

        zero = jnp.zeros(n_edges, jnp.uint32)
        rows, cols = jax.lax.fori_loop(0, scale, level, (zero, zero))
        if scramble:
            perm = jax.random.permutation(jax.random.fold_in(key, scale),
                                          n).astype(jnp.uint32)
            rows, cols = perm[rows], perm[cols]
        if symmetric:
            rows, cols = (jnp.concatenate([rows, cols]),
                          jnp.concatenate([cols, rows]))
        tile = (rows >> log_t) * tpr + (cols >> log_t)
        hi = (tile << log_t) | (rows & (T - 1))
        lo = cols & (T - 1)
        loop = rows == cols
        hi, lo, loop = jax.lax.sort((hi, lo, loop), num_keys=2)
        keep = jnp.concatenate([jnp.ones(1, bool),
                                (hi[1:] != hi[:-1]) | (lo[1:] != lo[:-1])])
        if symmetric:
            keep &= ~loop
        return hi, lo, keep

    hi, lo, keep = (np.asarray(x) for x in gen(G.seed_key(seed)))
    return hi[keep], lo[keep]


@pytest.mark.parametrize("scale,T,bucket_edges,graph500", [
    (10, 128, 1 << 26, True), (10, 128, 1000, True), (11, 256, 5000, True),
    (12, 512, 1 << 14, True), (12, 4096, 1 << 12, True),
    (10, 64, 3000, False), (11, 1024, 1 << 26, False)])
def test_bucketed_generator_equals_the_one_shot_sort(scale, T, bucket_edges,
                                                     graph500):
    """Buckets of source tile rows, each sorted and deduplicated alone and
    concatenated in order, give the one-shot sort's edge list bit for bit:
    the same draws, the same permutation, the same keys and key type."""
    seed = 2**33 + scale
    hi, lo = _one_shot(scale, 16, 0.57, 0.19, 0.19, seed, T, graph500,
                       graph500)
    g = G.rmat_device(scale, 16, 0.57, 0.19, 0.19, seed, T,
                      scramble=graph500, symmetric=graph500,
                      bucket_edges=bucket_edges)
    assert g.hi.dtype == np.uint32 and g.lo.dtype == np.uint32
    assert np.array_equal(g.hi, hi) and np.array_equal(g.lo, lo)
    counts = np.diff(G.tile_row_bounds(g.n, T, g.hi))
    buckets = G.plan_buckets(counts, bucket_edges, g.n)
    if bucket_edges < g.nnz // 2 and g.n > T:
        assert len(buckets) > 1


def test_plan_buckets_keeps_whole_tile_rows_under_the_cap():
    counts = np.array([3, 4, 0, 9, 1, 1, 2, 0])
    assert G.plan_buckets(counts, 8, 8) == [(0, 3, 7), (3, 4, 9),
                                            (4, 8, 4)]
    assert G.plan_buckets(counts, 100, 3) == [(0, 3, 7), (3, 6, 11),
                                              (6, 8, 2)]
    assert G.plan_buckets(np.zeros(2, int), 1, 4) == [(0, 2, 0)]


def test_keys_past_32_bits_go_through_the_layout_and_the_reference():
    """At n**2 / T > 2**32 the keys are uint64: the chunk layout and the
    reference's CSR both come out as the edge list."""
    from bench import reference

    n, T, C = 1 << 20, 16, 4
    assert G.key_dtype(n, T) is np.uint64
    assert G.key_dtype(1 << 22, 16384) is np.uint32
    rng = np.random.default_rng(11)
    rows = np.concatenate([rng.integers(0, n, 3000), [n - 1, n - 1, 0]])
    cols = np.concatenate([rng.integers(0, n, 3000), [n - 1, n - 2, 5]])
    tpr = n // T
    tile = (rows // T) * tpr + cols // T
    key = np.unique((tile * T + rows % T) * T + cols % T)
    hi = (key // T).astype(np.uint64)
    lo = (key % T).astype(np.uint32)
    assert hi.max() >= 1 << 32
    g = G.Graph(n, T, hi, lo)
    want_r, want_c = g.rows_cols()
    assert want_r.max() == n - 1 and want_c.max() == n - 1

    meta, rl, cl = G.chunk_layout(g, C, block_edges=700)
    got = []
    for (tr, tc, _, k), r, c in zip(meta, rl, cl):
        got.append(np.stack([tr * T + r[:k].astype(np.int64),
                             tc * T + c[:k].astype(np.int64)]))
    got = np.concatenate(got, axis=1)
    assert np.array_equal(got[0], want_r) and np.array_equal(got[1], want_c)
    assert set(meta[meta[:, 3] == 0, 0]) == (
        set(range(tpr)) - set(want_r // T))          # empty tile rows kept

    A = reference.csr(n, T, g.hi, g.lo)
    coo = A.tocoo()
    assert A.nnz == g.nnz
    assert np.array_equal(coo.row, want_r[np.lexsort((want_c, want_r))])
    order = np.lexsort((want_c, want_r))
    assert np.array_equal(coo.col, want_c[order])
