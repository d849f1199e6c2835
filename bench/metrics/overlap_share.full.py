"""Share of chunk batches whose staging overlapped the previous batch's
step, over every pass of the run (IOStats.overlap_batches)."""


def read(run):
    batches = run.passes * run.batches_per_pass
    if not batches:
        return None
    return run.io["overlap_batches"] / batches
