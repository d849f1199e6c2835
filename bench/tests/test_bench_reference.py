"""The plain reference against the engine, and the control against the
limit, at a size a CPU test run holds."""
import json
import os

import numpy as np
import pytest

from bench import graph as G
from bench import reference

CONFIG = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs",
                      "graph500-22.json")


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    with open(CONFIG) as f:
        g_cfg = json.load(f)["graph"]
    g = G.from_config(dict(g_cfg, scale=12), 21, 512)
    A = reference.csr(g.n, g.T, g.hi, g.lo)
    pool = np.asarray(np.random.default_rng(4).standard_normal((g.n, 64)),
                      np.float32)
    return g, A, pool, str(tmp_path_factory.mktemp("stores"))


def _limit():
    with open(CONFIG) as f:
        return json.load(f)["correct"]["max_rel_err"]


def test_csr_is_the_edge_list(tiny):
    g, A, _, _ = tiny
    rows, cols = g.rows_cols()
    dense = np.zeros((g.n, g.n))
    dense[rows, cols] = 1.0
    assert A.nnz == g.nnz
    assert np.array_equal(A.toarray(), dense)


@pytest.mark.parametrize("layout", ["raw", "packed"])
def test_reference_agrees_with_the_engine(tiny, layout):
    from repro.core.sem import SEMConfig, SEMSpMM
    from repro.io.storage import TileStore

    g, A, pool, root = tiny
    cfg = {"store": {"layout": layout, "binary": True, "T": 512, "C": 128}}
    path = G.build_stores(cfg, g, os.path.join(root, layout), lambda s: None)
    sem = SEMSpMM(TileStore.open(path), SEMConfig(chunk_batch=8))
    idx = np.array([3, 17, 40, 63])
    got = sem.multiply(pool[:, idx])
    err = reference.max_rel_err(A, pool, [(idx, got)])
    assert err <= _limit() / 10
    # a single entry altered by 0.1% is caught (a row of one edge, whose
    # error scale is the entry itself)
    bad = got.copy()
    bad[np.flatnonzero(np.diff(A.indptr) == 1)[0], 1] *= 1.001
    assert reference.max_rel_err(A, pool, [(idx, bad)]) > _limit()


def test_control_fails_the_limit(tiny):
    """The reference in the program's place with a bfloat16 operand reads
    far above the limit; a float32 one far below."""
    _, A, pool, _ = tiny
    reqs = [np.arange(k, k + 8) for k in range(0, 64, 8)]
    ctl = reference.control_answers(A, pool, reqs)
    assert reference.max_rel_err(A, pool, ctl) > 3 * _limit()
    f32 = [(idx, np.asarray(A.astype(np.float32) @ pool[:, idx]))
           for idx in reqs]
    assert reference.max_rel_err(A, pool, f32) < _limit() / 10


def test_wrong_shape_and_empty_rows_fail(tiny):
    _, A, pool, _ = tiny
    idx = np.array([0, 1])
    good = np.asarray(A @ pool[:, idx].astype(np.float64), np.float32)
    assert reference.max_rel_err(A, pool, [(idx, good[:-1])]) == np.inf
    empty = np.flatnonzero(np.diff(A.indptr) == 0)
    if empty.size:
        bad = good.copy()
        bad[empty[0], 0] = 1e-30
        assert reference.max_rel_err(A, pool, [(idx, bad)]) == np.inf
