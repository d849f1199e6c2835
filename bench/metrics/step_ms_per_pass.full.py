"""Device milliseconds of the batch step per pass in the traced window:
the step modules' device time over the passes booked in it (booked nnz
over the graph's nnz)."""
from bench.roofline import step_seconds, window_work


def read(run):
    secs = step_seconds(run)
    _, Z = window_work(run)
    if secs is None or not Z:
        return None
    return 1e3 * secs / (Z / run.nnz)
