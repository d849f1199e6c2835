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

The edges are drawn on the device in one jitted call, then sorted and
deduplicated in buckets of whole source tile rows (``rmat_device``), so
the device never holds more than one bucket's sort and a graph whose keys
pass 32 bits (``key_dtype``) is made in pieces.  Every seed gives the edge
list one sort of every edge would give.

``chunk_layout`` is a vectorised copy of ``repro.core.formats.to_chunked``
for edges already sorted by (tile, row, column): the same chunks, the same
order, the same first-of-tile-row flags, worked out one block of tile
rows at a time.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import time

import numpy as np


@dataclasses.dataclass
class Graph:
    """A deduplicated edge list in (tile, local row, local column) order:
    ``hi = tile * T + local_row`` and ``lo = local_col`` (uint32), where
    ``tile = tile_row * tiles_per_row + tile_col``.  ``hi`` is uint32
    where every key fits 32 bits (``tiles_per_row**2 * T <= 2**32``) and
    uint64 otherwise (``key_dtype``)."""
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


def key_dtype(n: int, T: int):
    """The unsigned type of ``Graph.hi`` for ``n`` vertices in tiles of
    ``T``: uint32 where the largest key fits, uint64 otherwise."""
    tpr = -(-n // T)
    return np.uint32 if tpr * tpr * T <= 1 << 32 else np.uint64


def tile_row_bounds(n: int, T: int, hi: np.ndarray) -> np.ndarray:
    """Index of the first edge of every tile row in a sorted ``hi``, and
    the edge count at the end (``tiles_per_row + 1`` entries)."""
    tpr = -(-n // T)
    starts = np.arange(tpr, dtype=np.int64) * tpr * T
    return np.append(np.searchsorted(hi, starts.astype(hi.dtype)),
                     hi.shape[0])


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


# edges one bucket sorts on the device: about 0.6 GB of sort operands
BUCKET_EDGES = 1 << 26


def plan_buckets(counts: np.ndarray, cap: int, max_rows: int):
    """Split tile rows into consecutive ranges ``[r0, r1)`` of at most
    ``max_rows`` rows whose edge counts (``counts[r]`` for tile row ``r``)
    sum to at most ``cap``; a tile row holding more than ``cap`` edges is a
    range of its own.  Returns ``[(r0, r1, edges), ...]``."""
    out, r0, total = [], 0, 0
    for r, k in enumerate(int(k) for k in counts):
        if r > r0 and (total + k > cap or r - r0 >= max_rows):
            out.append((r0, r, total))
            r0, total = r, 0
        total += k
    out.append((r0, len(counts), total))
    return out


def edge_drawer(scale: int, n_edges: int, a: float, b: float, c: float,
                scramble: bool):
    """The jitted draw of ``n_edges`` directed R-MAT edges from a key:
    (rows, cols) uint32 on the device, relabelled with ``scramble``."""
    import jax
    import jax.numpy as jnp

    n = 1 << scale

    @jax.jit
    def draw(key):
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
        return rows, cols

    return draw


def bucket_sorter(T: int, tpr: int, symmetric: bool):
    """The jitted sort and deduplication of one bucket: ``(src, dst,
    count, r0, cap)`` holds ``count`` edges of source tile rows from
    ``r0`` in ``cap`` slots; returns (hi, lo) keys local to the bucket in
    (tile, row, col) order and the flags of the edges kept (the first of
    equal keys; no self-loop where ``symmetric``)."""
    import jax
    import jax.numpy as jnp

    log_t = T.bit_length() - 1

    @functools.partial(jax.jit, static_argnums=4)
    def sort_bucket(src, dst, count, r0, cap):
        # keys local to the bucket: (tile - r0 * tpr) * T + local row; the
        # padding sorts last (lo = 2**32 - 1 is no local column)
        pad = jnp.arange(cap) >= count
        tile = ((src >> log_t) - r0) * tpr + (dst >> log_t)
        none = jnp.uint32(0xFFFFFFFF)
        hi = jnp.where(pad, none, (tile << log_t) | (src & (T - 1)))
        lo = jnp.where(pad, none, dst & (T - 1))
        loop = src == dst
        hi, lo, loop = jax.lax.sort((hi, lo, loop), num_keys=2)
        keep = jnp.concatenate([jnp.ones(1, bool),
                                (hi[1:] != hi[:-1]) | (lo[1:] != lo[:-1])])
        if symmetric:
            keep &= ~loop
        return hi, lo, keep

    return sort_bucket


def rmat_device(scale: int, edge_factor: int, a: float, b: float, c: float,
                seed: int, T: int, *, scramble: bool = False,
                symmetric: bool = False,
                bucket_edges: int = BUCKET_EDGES) -> Graph:
    """Generate and relabel (``scramble``) on the device, then symmetrise
    without self-loops (``symmetric``), sort by (tile, row, col) and
    deduplicate one bucket of source tile rows at a time: the host splits
    the edges by the tile row of their source, the device sorts and
    deduplicates each bucket, and the buckets in tile-row order are the
    whole graph in (tile, row, col) order.  No bucket holds more than
    ``bucket_edges`` edges unless one tile row does."""
    n = 1 << scale
    if T & (T - 1) or T > n:
        raise ValueError(f"tile size {T} must be a power of two <= {n}")
    tpr = n // T
    n_edges = edge_factor * n
    log_t = T.bit_length() - 1

    draw = edge_drawer(scale, n_edges, a, b, c, scramble)
    sort_bucket = bucket_sorter(T, tpr, symmetric)
    rows, cols = (np.asarray(x) for x in draw(seed_key(seed)))
    src_row = [rows >> log_t] + ([cols >> log_t] if symmetric else [])
    counts = sum(np.bincount(r, minlength=tpr) for r in src_row)
    dtype = key_dtype(n, T)
    total = int(counts.sum())
    hi_all = np.empty(total, dtype)
    lo_all = np.empty(total, np.uint32)
    # one sort shape for every bucket of every seed: the configuration's
    # edge count caps it
    width = min(bucket_edges, 1 << (total - 1).bit_length())
    m = 0
    for r0, r1, k in plan_buckets(counts, width, (1 << 32) // n):
        if not k:
            continue
        cap = max(width, 1 << (k - 1).bit_length())
        src = np.zeros(cap, np.uint32)
        dst = np.zeros(cap, np.uint32)
        j = 0
        for sr, s, d in zip(src_row, (rows, cols), (cols, rows)):
            sel = (sr >= r0) & (sr < r1)
            j2 = j + int(np.count_nonzero(sel))
            src[j:j2], dst[j:j2] = s[sel], d[sel]
            j = j2
        hi, lo, keep = (np.asarray(x)[:k] for x in
                        sort_bucket(src, dst, k, r0, cap))
        u = int(np.count_nonzero(keep))
        hi_all[m:m + u] = hi[keep]
        hi_all[m:m + u] += dtype(r0 * tpr * T)
        lo_all[m:m + u] = lo[keep]
        m += u
    return Graph(n, T, hi_all[:m], lo_all[:m])


def chunk_layout(g: Graph, C: int, block_edges: int = 1 << 23):
    """(meta int32 (n_chunks, 4), row_local uint16 (n_chunks, C),
    col_local uint16 (n_chunks, C)) exactly as ``to_chunked`` lays the
    same matrix out: C-entry chunks of one tile each in (tile_row,
    tile_col) order, one empty chunk for a tile row without entries,
    ``meta = (tile_row, tile_col, first_of_tile_row, nnz)``.  Edges are
    read in blocks of whole tile rows of about ``block_edges`` entries, so
    no per-edge temporary is larger than one block."""
    T, tpr = g.T, g.tiles_per_row
    ntr = -(-g.n // T)
    log_t = T.bit_length() - 1
    bounds = tile_row_bounds(g.n, T, g.hi)
    blocks = [(bounds[r0], bounds[r1]) for r0, r1, _ in
              plan_buckets(np.diff(bounds), block_edges, ntr)]

    # the tiles present, their first edge and their entry counts
    tile_ids, starts = [], []
    for a, b in blocks:
        tile = (g.hi[a:b] >> log_t).astype(np.int64)
        s = np.flatnonzero(np.diff(tile, prepend=-1))
        tile_ids.append(tile[s])
        starts.append(s + a)
    tile_ids = np.concatenate(tile_ids)
    starts = np.concatenate(starts)
    counts = np.diff(np.append(starts, g.nnz))
    cpt = -(-counts // C)
    base = np.concatenate([[0], np.cumsum(cpt)[:-1]]).astype(np.int64)
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

    # every edge to its chunk and lane, one block of tiles at a time
    row_l = np.zeros(n_chunks * C, np.uint16)
    col_l = np.zeros(n_chunks * C, np.uint16)
    t0 = 0
    for a, b in blocks:
        t1 = int(np.searchsorted(starts, b))
        cnt = counts[t0:t1]
        within_e = np.arange(a, b) - np.repeat(starts[t0:t1], cnt)
        entry_chunk = np.repeat(base[t0:t1], cnt) + within_e // C
        pos = final_real[entry_chunk] * C + within_e % C
        row_l[pos] = g.hi[a:b] & (T - 1)
        col_l[pos] = g.lo[a:b]
        t0 = t1
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
