"""Host time of AGE building fresh hardware rows per 1,000 design points,
in milliseconds: the union of the program's ``repro.age.generate`` spans
in the traced window (`bench.program_spans`)."""

from bench import program_spans as ps


def read(run):
    sp = ps.load(run)
    if sp is None or run.mode not in ("sweep", "frontier"):
        return None
    return ps.ms_per_kpoint(sp, (ps.AGE,), run.points)
