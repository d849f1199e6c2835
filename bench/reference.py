"""The plain reference and the comparison that decides ``correct``.

The reference is SciPy CSR arithmetic in float64 over the generated edge
list; it imports nothing of the program.  Every request multiplies the
binary adjacency matrix ``A`` by columns drawn from the seeded operand
pool, so one blocked ``A @ pool`` answers every request.

A delivered float32 column is held to the reference entry by entry,
against the scale of float32 summation error for that entry,
``(|A| @ |x|)_i``: the number compared is the largest
``|got_i - ref_i| / (|A| @ |x|)_i`` over every sampled answer.  A row of
``A`` with no entries has scale 0 and must come back exactly 0.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Sequence, Tuple

import numpy as np
import scipy.sparse as sp

COL_BLOCK = 16
THREADS = min(16, os.cpu_count() or 1)


def csr(n: int, T: int, hi: np.ndarray, lo: np.ndarray) -> sp.csr_matrix:
    """The binary adjacency matrix of a ``graph.Graph`` edge list (unique
    edges sorted by tile, local row, local column), row-major.  Built one
    tile row at a time (a stable sort of its local rows), so no temporary
    is larger than one tile row's edges."""
    tpr = -(-n // T)
    N = hi.shape[0]
    starts = np.arange(tpr, dtype=np.int64) * tpr * T
    bounds = np.append(np.searchsorted(hi, starts.astype(hi.dtype)), N)
    indices = np.empty(N, np.int32)
    counts = np.zeros(tpr * T, np.int64)
    for r in range(tpr):
        a, b = bounds[r], bounds[r + 1]
        h = hi[a:b].astype(np.int64)
        local = h % T
        order = np.argsort(local, kind="stable")
        col = (h // T - r * tpr) * T + lo[a:b]
        indices[a:b] = col[order]
        counts[r * T:(r + 1) * T] = np.bincount(local, minlength=T)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(counts[:n], out=indptr[1:])
    return sp.csr_matrix((np.ones(N), indices, indptr), shape=(n, n))


def matmul(A: sp.csr_matrix, x: np.ndarray) -> np.ndarray:
    """``A @ x`` over row blocks on a few threads."""
    n = A.shape[0]
    cuts = [i * n // THREADS for i in range(THREADS + 1)]
    with ThreadPoolExecutor(THREADS) as ex:
        parts = ex.map(lambda ab: A[ab[0]:ab[1]] @ x, zip(cuts, cuts[1:]))
        return np.concatenate(list(parts))


def max_rel_err(A: sp.csr_matrix, pool: np.ndarray,
                answers: Sequence[Tuple[np.ndarray, np.ndarray]]) -> float:
    """Largest scaled error over ``answers``: pairs of (pool column indices
    of a request, the (n, p) result delivered for it).  Computed in blocks
    of pool columns, so at most ``COL_BLOCK`` reference columns are live."""
    need: Dict[int, List[Tuple[np.ndarray, int]]] = {}
    for idx, got in answers:
        if got.shape != (A.shape[0], len(idx)):
            return float("inf")
        for pos, j in enumerate(idx):
            need.setdefault(int(j), []).append((got, pos))
    worst = 0.0
    cols = sorted(need)
    for b0 in range(0, len(cols), COL_BLOCK):
        blk = cols[b0:b0 + COL_BLOCK]
        x = pool[:, blk].astype(np.float64)
        both = matmul(A, np.concatenate([x, np.abs(x)], axis=1))
        ref, scale = both[:, :len(blk)], both[:, len(blk):]
        for k, j in enumerate(blk):
            s = scale[:, k]
            zero = s == 0
            for got, pos in need[j]:
                diff = np.abs(got[:, pos].astype(np.float64) - ref[:, k])
                if np.any(diff[zero] != 0):
                    return float("inf")
                worst = max(worst, float(np.max(diff[~zero] / s[~zero],
                                                initial=0.0)))
    return worst


def control_answers(A: sp.csr_matrix, pool: np.ndarray,
                    requests: Sequence[np.ndarray]):
    """The control: the reference in the program's place, one precision
    down from the configuration's float32 operand — the operand rounded to
    bfloat16, products summed in float32.  One blocked product over the
    pool answers every request."""
    import ml_dtypes
    A32 = A.astype(np.float32)
    low = pool.astype(ml_dtypes.bfloat16).astype(np.float32)
    full = np.concatenate([matmul(A32, low[:, b:b + COL_BLOCK])
                           for b in range(0, pool.shape[1], COL_BLOCK)],
                          axis=1)
    return [(idx, full[:, idx]) for idx in requests]
