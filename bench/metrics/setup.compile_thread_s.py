"""Seconds spent lowering and compiling the evaluator's executables during
set-up, summed over the compile-service threads (so it can exceed wall
time): the delta of `pathfinder.compile_cache_stats()["compile_seconds"]`
over set-up."""


def read(run):
    return float(run.setup["compile_seconds"])
