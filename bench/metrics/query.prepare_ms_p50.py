"""Median time of a query's runner prologue, in milliseconds: over the
traced window's ``repro.runner.run`` spans, the union of the
``repro.runner.prepare`` spans inside each (`bench.program_spans`).  The
prologue enumerates the design points (meshes, strategies, hardware axes)
and their chunks and opens the outputs, before the first pack.  None
where the program marks no prologue."""

from bench import program_spans as ps

PREPARE = "repro.runner.prepare"


def read(run):
    sp = ps.load(run)
    if sp is None or run.mode != "query" or not sp.of((PREPARE,)):
        return None
    return ps.median([sp.covered_ns((PREPARE,), s, e) / 1e6
                      for s, e in sp.runs()])
