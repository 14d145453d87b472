"""Share of the sweep window in which no operation runs on the device
while the dispatching thread (the one that holds ``repro.runner.run``)
waits for the producer (``repro.pipeline.wait_pack``) or runs it inline
(``repro.pipeline.pack``), in percent (`bench.program_spans`)."""

from bench import program_spans as ps


def read(run):
    sp = ps.load(run)
    if sp is None or run.mode not in ("sweep", "frontier"):
        return None
    return ps.idle_behind_producer(sp)
