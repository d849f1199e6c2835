"""Quickstart: the paper's core object — semi-external-memory SpMM.

Builds a power-law graph, converts it to the SCSR+COO tiled format, runs
the same multiply three ways (flat-COO oracle, in-memory tiled, semi-
external streaming), validates they agree, and prints the format/IO stats
that make the paper's argument.

  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.apps.common import IMOperator, SEMOperator
from repro.compile_cache import enable_compile_cache
from repro.core.formats import CSR, from_coo_tiled
from repro.core.spmm import spmm_coo
from repro.sparse.generate import rmat

import jax.numpy as jnp


def main():
    enable_compile_cache()
    print("== build a scaled power-law graph (R-MAT) ==")
    g = rmat(16, 16, seed=0)  # 65k vertices, ~1M edges
    print(f"graph: {g.n_rows:,} vertices, {g.nnz:,} edges")

    print("\n== the paper's format: SCSR+COO tiles ==")
    ts = from_coo_tiled(g, t=16384)
    csr = CSR.from_coo(g)
    print(f"SCSR   : {ts.nbytes(0)/1e6:8.2f} MB  (2B row headers + 2B cols)")
    print(f"DCSC   : {ts.dcsc_nbytes(0)/1e6:8.2f} MB  "
          f"(SCSR/DCSC = {ts.nbytes(0)/ts.dcsc_nbytes(0):.2f}, "
          f"paper: 0.45-0.70 for real graphs)")
    print(f"CSR    : {csr.nbytes(0)/1e6:8.2f} MB  (the MKL/Tpetra baseline)")

    print("\n== one multiply, three execution tiers ==")
    rng = np.random.default_rng(0)
    x = rng.standard_normal((g.n_cols, 4)).astype(np.float32)
    oracle = np.asarray(spmm_coo(g, jnp.asarray(x)))

    im = IMOperator.from_coo(g)
    y_im = im.dot(x)
    np.testing.assert_allclose(y_im, oracle, rtol=2e-4, atol=2e-4)
    print("IM-SpMM  (tiled, in-memory)      : OK, matches oracle")

    sem = SEMOperator.from_coo(g)
    y_sem = sem.dot(x)
    np.testing.assert_allclose(y_sem, oracle, rtol=2e-4, atol=2e-4)
    print("SEM-SpMM (streamed from 'SSD')   : OK, matches oracle")
    print(f"  bytes streamed: {sem.io_bytes_read/1e6:.1f} MB "
          f"(the sparse matrix, read once per multiply)")
    print(f"  resident memory: dense columns only "
          f"({4*g.n_rows*4*2/1e6:.1f} MB) — the SEM contract")


if __name__ == "__main__":
    main()
