"""Device time of the evaluator's executables per query, in milliseconds,
from the profiler trace: the sweep pipeline's fused per-design functions,
the compile-ahead buckets and the frontier steps (named in the trace as
below), summed over the window and divided by the queries it answered."""

EXECUTABLES = ("jit_design", "jit_step", "jit_scalar")


def read(run):
    if run.summary is None or run.mode != "query" or not run.latencies_s:
        return None
    s = sum(v for k, v in run.summary.module_s.items()
            if k.startswith(EXECUTABLES))
    return 1e3 * s / len(run.latencies_s) if s > 0 else None
