"""Median due-to-answer seconds over every request due in the window."""
from bench.meter import quantile


def read(run):
    lat = [r.done - r.due if r.done is not None else float("inf")
           for r in run.attempted]
    return quantile(lat, 0.5)
