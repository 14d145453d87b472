"""Median host time of a query's evaluation (`SweepRunner.run` of its
grid), from the harness's span around the call, in milliseconds."""

import statistics


def read(run):
    xs = run.spans.get("bench.query.eval")
    return 1e3 * statistics.median(xs) if xs else None
