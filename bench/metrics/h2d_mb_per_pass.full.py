"""Host-to-device bytes the engine staged per pass (chunk planes, operand
columns, mid-pass column writes), over every pass of the run, in MB
(IOStats.h2d_bytes)."""


def read(run):
    if not run.passes:
        return None
    return run.io["h2d_bytes"] / run.passes / 1e6
