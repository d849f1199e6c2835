"""Share of the tenants of passes that ended in the window which joined
mid-pass (PassReport.admitted_midpass over those plus the tenants a pass
started with)."""


def read(run):
    mid = sum(r.admitted_midpass for r in run.reports)
    total = mid + sum(r.tenants for r in run.reports)
    return mid / total if total else None
