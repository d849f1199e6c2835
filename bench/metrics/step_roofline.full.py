"""The batch step's share of its roofline in the traced window: the least
time the chip could take for the booked work (roofline.py) over the step
modules' device time, in percent."""
from bench.roofline import bound_seconds, step_seconds, window_work


def read(run):
    secs = step_seconds(run)
    E, Z = window_work(run)
    if secs is None or not E or run.peaks is None:
        return None
    return 100.0 * bound_seconds(run, E, Z) / secs
