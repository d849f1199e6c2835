"""95th percentile of due-to-answer seconds over every request due in the
window; a request never answered counts as infinitely late."""
from bench.meter import quantile


def read(run):
    lat = [r.done - r.due if r.done is not None else float("inf")
           for r in run.attempted]
    return quantile(lat, 0.95)
