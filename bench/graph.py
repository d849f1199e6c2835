"""The benchmark's graph: a Graph500 Kronecker (R-MAT) edge list made on
the device from the seed, laid out as the engine's chunked tiles, and
ingested through the program's own store writers.

The generator copies the semantics of ``repro.sparse.generate.rmat``: every
edge draws one quadrant per bit level, the row bit set with probability
``c + d`` and the column bit with ``d / (c + d)`` under a set row bit and
``b / (a + b)`` otherwise; duplicate edges are dropped.  It draws its
uniforms with ``jax.random`` on the device, so it is fast, and its edges
differ from the program's generator for the same seed (the semantics are
the same; ``tests/test_bench_graph.py`` checks both).

A Graph500 graph is undirected and its vertex labels are scrambled: with
``scramble`` every label goes through one seeded random permutation of
``[0, n)``, and with ``symmetric`` each edge is stored both ways and
self-loops are dropped, so the adjacency matrix is the symmetric 0/1
matrix of a simple graph.

``chunk_layout`` is a vectorised copy of ``repro.core.formats.to_chunked``
for edges already sorted by (tile, row, column): the same chunks, the same
order, the same first-of-tile-row flags.
"""
from __future__ import annotations

import dataclasses
import os
import time

import numpy as np


@dataclasses.dataclass
class Graph:
    """A deduplicated edge list in (tile, local row, local column) order:
    ``hi = tile * T + local_row`` and ``lo = local_col``, both uint32, where
    ``tile = tile_row * tiles_per_row + tile_col``."""
    n: int
    T: int
    hi: np.ndarray
    lo: np.ndarray

    @property
    def nnz(self) -> int:
        return int(self.hi.shape[0])

    @property
    def tiles_per_row(self) -> int:
        return -(-self.n // self.T)

    def rows_cols(self):
        """Global (row, col) int64 coordinates of every edge."""
        tile = self.hi.astype(np.int64) // self.T
        row = (tile // self.tiles_per_row) * self.T + self.hi % self.T
        col = (tile % self.tiles_per_row) * self.T + self.lo.astype(np.int64)
        return row, col


def rmat_bits(u_row, u_col, a: float, b: float, c: float, xp=np):
    """One bit level of R-MAT from two uniforms per edge: (row bit, col
    bit), as ``repro.sparse.generate.rmat`` draws them."""
    d = 1.0 - a - b - c
    rbit = u_row < (c + d)
    p_col1 = xp.where(rbit, d / (c + d), b / (a + b))
    cbit = u_col < p_col1
    return rbit, cbit


def seed_key(seed: int):
    """A JAX key from a seed of any size (its low and high 32 bits)."""
    import jax
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def from_config(g_cfg: dict, seed: int, T: int) -> Graph:
    """The graph a configuration's ``graph`` group describes."""
    return rmat_device(g_cfg["scale"], g_cfg["edge_factor"], g_cfg["a"],
                       g_cfg["b"], g_cfg["c"], seed, T,
                       scramble=g_cfg["scramble"],
                       symmetric=g_cfg["symmetric"])


def rmat_device(scale: int, edge_factor: int, a: float, b: float, c: float,
                seed: int, T: int, *, scramble: bool = False,
                symmetric: bool = False) -> Graph:
    """Generate, relabel (``scramble``), symmetrise without self-loops
    (``symmetric``), sort by (tile, row, col) and deduplicate on the
    device; the host receives the unique edges."""
    import jax
    import jax.numpy as jnp

    n = 1 << scale
    if T & (T - 1) or T > n:
        raise ValueError(f"tile size {T} must be a power of two <= {n}")
    tpr = n // T
    if tpr * tpr * T > 1 << 32:
        raise ValueError(f"scale {scale} at T={T} overflows the uint32 keys")
    n_edges = edge_factor * n
    log_t = T.bit_length() - 1

    @jax.jit
    def gen(key):
        def level(i, rc):
            rows, cols = rc
            u = jax.random.uniform(jax.random.fold_in(key, i), (2, n_edges),
                                   jnp.float32)
            rbit, cbit = rmat_bits(u[0], u[1], a, b, c, xp=jnp)
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

    hi, lo, keep = (np.asarray(a) for a in gen(seed_key(seed)))
    return Graph(n, T, hi[keep], lo[keep])


def chunk_layout(g: Graph, C: int):
    """(meta int32 (n_chunks, 4), row_local uint16 (n_chunks, C),
    col_local uint16 (n_chunks, C)) exactly as ``to_chunked`` lays the
    same matrix out: C-entry chunks of one tile each in (tile_row,
    tile_col) order, one empty chunk for a tile row without entries,
    ``meta = (tile_row, tile_col, first_of_tile_row, nnz)``."""
    T, tpr = g.T, g.tiles_per_row
    ntr = -(-g.n // T)
    N = g.nnz
    tile = (g.hi >> np.uint32(T.bit_length() - 1)).astype(np.int64)
    starts = np.flatnonzero(np.diff(tile, prepend=-1))
    counts = np.diff(np.append(starts, N))
    tile_ids = tile[starts]
    cpt = -(-counts // C)
    base = np.concatenate([[0], np.cumsum(cpt)[:-1]])
    n_real = int(cpt.sum())
    chunk_tile = np.repeat(np.arange(len(cpt)), cpt)
    within_c = np.arange(n_real) - base[chunk_tile]
    chunk_trow = tile_ids[chunk_tile] // tpr
    present = np.zeros(ntr, bool)
    present[tile_ids // tpr] = True
    empty = np.flatnonzero(~present)
    final_real = np.arange(n_real) + np.searchsorted(empty, chunk_trow)
    final_empty = (np.searchsorted(chunk_trow, empty)
                   + np.arange(len(empty)))
    n_chunks = n_real + len(empty)
    meta = np.zeros((n_chunks, 4), np.int32)
    meta[final_real, 0] = chunk_trow
    meta[final_real, 1] = tile_ids[chunk_tile] % tpr
    meta[final_real, 3] = np.minimum(counts[chunk_tile] - within_c * C, C)
    meta[final_empty, 0] = empty
    meta[0, 2] = 1
    meta[1:, 2] = meta[1:, 0] != meta[:-1, 0]

    within_e = np.arange(N) - np.repeat(starts, counts)
    entry_chunk = np.repeat(base, counts) + within_e // C
    pos = final_real[entry_chunk] * C + within_e % C
    row_l = np.zeros(n_chunks * C, np.uint16)
    col_l = np.zeros(n_chunks * C, np.uint16)
    row_l[pos] = g.hi & np.uint32(T - 1)
    col_l[pos] = g.lo
    return meta, row_l.reshape(n_chunks, C), col_l.reshape(n_chunks, C)


def build_stores(cfg: dict, g: Graph, workdir: str, log) -> str:
    """Write the configuration's store under ``workdir`` through the
    program's ingest (``TileStore.write``; ``TileStore.optimize`` for the
    packed layout) and return its path."""
    from repro.core.formats import ChunkedTiles
    from repro.io.storage import TileStore

    st = cfg["store"]
    T, C = st["T"], st["C"]
    os.makedirs(workdir, exist_ok=True)
    t0 = time.perf_counter()
    meta, row_l, col_l = chunk_layout(g, C)
    t1 = time.perf_counter()
    raw = os.path.join(workdir, "raw")
    store = TileStore.write(raw, ChunkedTiles(g.n, g.n, T, C, meta, row_l,
                                              col_l, None),
                            binary=st["binary"])
    t2 = time.perf_counter()
    log(f"ingest: {meta.shape[0]} chunks, raw store {store.nbytes} B; "
        f"chunk layout {t1 - t0:.3f} s, TileStore.write {t2 - t1:.3f} s")
    if st["layout"] == "raw":
        return raw
    if st["layout"] != "packed":
        raise ValueError(f"unknown store layout {st['layout']!r}")
    packed = os.path.join(workdir, "packed")
    opt = store.optimize(packed)
    log(f"ingest: packed store {opt.nbytes} B; TileStore.optimize "
        f"{time.perf_counter() - t2:.3f} s")
    return packed
