"""Edge-column products per second over whole passes: for each pass that
ended in the window, the nonzeros of each of its chunk batches times the
live tenant columns the batch was multiplied against, over the time from
the end of the pass before the first of them to the end of the last
(host clock; ``meter.BatchMeter.whole_passes``)."""


def read(run):
    done = run.meter.whole_passes(run.t0, run.t1)
    if done is None:
        return None
    work, seconds, _ = done
    return work / seconds
