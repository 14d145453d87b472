"""Median AGE time per query, in milliseconds: over the traced window's
``repro.runner.run`` spans, the union of the ``repro.age.generate`` spans
inside each, on any thread (`bench.program_spans`)."""

from bench import program_spans as ps


def read(run):
    sp = ps.load(run)
    if sp is None or run.mode != "query":
        return None
    return ps.median(ps.age_ms_per_run(sp))
