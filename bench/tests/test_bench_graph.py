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


@pytest.mark.parametrize("scale,T,C", [(10, 128, 64), (12, 1024, 256),
                                       (9, 512, 32)])
def test_chunk_layout_matches_to_chunked(scale, T, C):
    from repro.core.formats import COO, to_chunked

    g = G.rmat_device(scale, 16, 0.57, 0.19, 0.19, scale, T)
    rows, cols = g.rows_cols()
    ct = to_chunked(COO(g.n, g.n, rows, cols, None), T=T, C=C)
    meta, rl, cl = G.chunk_layout(g, C)
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
