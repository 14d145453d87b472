"""Host time of the sweep pipeline's producer per 1,000 design points, in
milliseconds: the union of the program's ``repro.pipeline.pack`` spans
(label resolution, fresh hardware rows through AGE and ``pack_hw``,
compile-ahead submission) in the traced window (`bench.program_spans`)."""

from bench import program_spans as ps


def read(run):
    sp = ps.load(run)
    if sp is None or run.mode not in ("sweep", "frontier"):
        return None
    return ps.ms_per_kpoint(sp, (ps.PACK,), run.points)
