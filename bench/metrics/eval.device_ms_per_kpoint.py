"""Device time of the evaluator's executables per 1,000 design points, in
milliseconds, from the profiler trace.  The executables are the sweep
pipeline's fused per-design functions, the compile-ahead buckets and the
frontier steps, named in the trace as below."""

EXECUTABLES = ("jit_design", "jit_step", "jit_scalar")


def read(run):
    if run.summary is None or not run.points:
        return None
    s = sum(v for k, v in run.summary.module_s.items()
            if k.startswith(EXECUTABLES))
    return 1e3 * s / (run.points / 1e3) if s > 0 else None
