"""The work a pass of ``A @ X`` needs, from the graph's sizes alone, so
every implementation is held to the same work.

Per pass over ``nnz`` stored entries with ``live`` tenant columns:
operations ``2 * nnz * live``; bytes ``2 * n * live * 4`` (the float32
operand read once and the accumulator written once) plus one byte per
nonzero, the least any store encoding of this repository ships per entry.
Counting bytes low can only lower a share of the roofline.

A window is booked batch by batch (``meter.BatchMeter``, at each batch's
completion on the device): with ``E`` the
sum of batch nnz times live columns and ``Z`` the sum of batch nnz, the
window holds ``Z / nnz`` passes, operations ``2 E`` and bytes
``8 n E / nnz + Z``.

On a cell of ``k`` chips the work is the same and the chip's peaks count
``k`` times; the step takes as long as the chip with the most step time,
which paces the pass.
"""
from __future__ import annotations

from typing import Optional

# the engine's batch step: the scan steps of core/sem.py, the Pallas wave
# kernel of kernels/sem_spmm.py
STEP_MODULES = ("_batch_step", "spmm_pallas", "sem_spmm")


def window_work(run):
    """(edge-column products E, nonzeros Z) done in the traced window."""
    recs = run.meter.in_window(run.t0, run.t1)
    return (sum(z * live for _, z, live, _ in recs),
            sum(z for _, z, _, _ in recs))


def bound_seconds(run, E: int, Z: int) -> float:
    """Least time the cell's chips could take for the booked work: the
    larger of operations over their peak FLOP/s and bytes over their peak
    HBM bandwidth."""
    ops = 2.0 * E
    nbytes = 8.0 * run.n * E / run.nnz + Z
    return max(ops / (run.chips * run.peaks["flops_per_s"]),
               nbytes / (run.chips * run.peaks["hbm_bytes_per_s"]))


def step_seconds(run) -> Optional[float]:
    """Device seconds of the batch step's modules in the traced window, on
    the chip with the most of them."""
    if run.trace is None:
        return None
    secs = max(sum(run.trace.module_seconds(STEP_MODULES, chip).values())
               for chip in range(run.trace.chips))
    return secs if secs > 0 else None
