"""Device-to-host bytes of results read back per pass (the pass's
product and mid-pass partial reads), over every pass of the run, in MB
(IOStats.d2h_bytes); None where the program does not count them."""


def read(run):
    if not run.passes or "d2h_bytes" not in run.io:
        return None
    return run.io["d2h_bytes"] / run.passes / 1e6
