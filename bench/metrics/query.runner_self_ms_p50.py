"""Median self time of a query's `SweepRunner.run`, in milliseconds: over
the traced window's ``repro.runner.run`` spans, each span's length less
the union of the other ``repro.*`` spans inside it, on any thread
(`bench.program_spans`)."""

from bench import program_spans as ps


def read(run):
    sp = ps.load(run)
    if sp is None or run.mode != "query":
        return None
    return ps.median(ps.self_ms_per_run(sp))
