"""Host time of the sweep pipeline's writer per 1,000 design points, in
milliseconds: the union of the program's ``repro.pipeline.finalize``
(block on device results, metric fold) and ``repro.runner.commit`` (JSONL
or checkpoint commit) spans in the traced window (`bench.program_spans`)."""

from bench import program_spans as ps


def read(run):
    sp = ps.load(run)
    if sp is None or run.mode not in ("sweep", "frontier"):
        return None
    return ps.ms_per_kpoint(sp, (ps.FINALIZE, ps.COMMIT), run.points)
