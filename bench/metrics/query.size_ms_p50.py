"""Median host time of a query's sizing (`traffic.size_fleet`), from the
harness's span around the call, in milliseconds."""

import statistics


def read(run):
    xs = run.spans.get("bench.query.size")
    return 1e3 * statistics.median(xs) if xs else None
