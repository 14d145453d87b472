"""Share of the query window in which no operation ran on the device, in
percent, from the profiler trace (`bench.trace`)."""


def read(run):
    if run.summary is None or run.mode != "query":
        return None
    return 100.0 * run.summary.idle_share
