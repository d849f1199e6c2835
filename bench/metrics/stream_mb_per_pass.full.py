"""Store bytes streamed per pass (read from the slow tier plus served by
the hot-chunk cache), over every pass of the run, in MB (IOStats)."""


def read(run):
    if not run.passes:
        return None
    io = run.io
    return (io["bytes_read"] + io["cache_hit_bytes"]) / run.passes / 1e6
