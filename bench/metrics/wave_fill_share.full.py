"""Live tenant columns over the waves' packed width, weighted by the
nonzeros of each batch dispatched in the window (the scheduler's active
tenants at each batch boundary)."""


def read(run):
    recs = run.meter.in_window(run.t0, run.t1)
    cap = sum(z * c for _, z, _, c in recs)
    if not cap:
        return None
    return sum(z * live for _, z, live, _ in recs) / cap
