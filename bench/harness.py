"""One run of one benchmark cell (see ``bench/run.py`` for the command).

Set-up (counted in ``setup_s``): import, device check, the persistent
compilation cache, and the traffic mix's warm-up requests, which have the
window's shapes.  The window then serves the mix's requests back to back
for ``--seconds`` and lets the one in flight finish.  After it: the
device's peak memory, the correctness check against the reference on the
host CPU, and, with ``--trace 1``, the reduction of the profiler trace
that covered the window.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time
import traceback
from typing import Dict, List, Optional

from bench import manifest

WORKDIR = os.path.join(manifest.ROOT, ".bench_run")


@dataclasses.dataclass
class Run:
    """What the per-layer metric readers read."""

    mode: str
    points: int                         # design points answered in the window
    window_s: float
    latencies_s: List[float]            # per query, start to sizing answer
    spans: Dict[str, List[float]]       # harness host spans in the window
    setup: Dict[str, float]             # counter deltas over set-up
    summary: Optional[object] = None    # bench.trace.Summary (--trace 1)


def percentile(values: List[float], q: float) -> float:
    """The q-th percentile, linear between order statistics."""
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


END_TO_END = {
    "points_per_s": lambda run, setup_s: run.points / run.window_s,
    "query_ms_p50": lambda run, setup_s: 1e3 * percentile(run.latencies_s, 50),
    "query_ms_p95": lambda run, setup_s: 1e3 * percentile(run.latencies_s, 95),
    "setup_s": lambda run, setup_s: setup_s,
}


def parse(argv):
    ap = argparse.ArgumentParser(prog="bench/run.py",
                                 description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def check_device(chips: int):
    """The devices to measure on, or a message saying why there are none."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        return None, (f"JAX finds no TPU (its first device is "
                      f"{devs[0].platform!r}); nothing was measured")
    if len(devs) < chips:
        return None, (f"the cell needs {chips} TPU chips, JAX finds "
                      f"{len(devs)}")
    return devs, None


def _counters() -> Dict[str, float]:
    from repro.core import pathfinder
    return dict(pathfinder.compile_cache_stats())


def _delta(a: Dict[str, float], b: Dict[str, float]) -> Dict[str, float]:
    return {k: b[k] - a.get(k, 0) for k in b}


def _trace_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # harness spans and device ops only
    opts.host_tracer_level = 1
    return opts


def serve_window(driver, reqs, seconds: float):
    """Serve requests back to back until ``seconds`` have passed; the one
    in flight then finishes.  Returns (attempted, failed, window seconds)."""
    import jax

    from bench import trace as btrace
    attempted = failed = 0
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(btrace.WINDOW_SPAN):
        for req in reqs.window():
            attempted += 1
            try:
                driver.done.append(driver.serve(req))
            except Exception:           # a request that fails is counted
                failed += 1
                traceback.print_exc(file=sys.stderr)
            if time.perf_counter() - t0 >= seconds:
                break
    return attempted, failed, time.perf_counter() - t0


def check_run(driver, seed: int, workdir: str, stand_in=None):
    """The correctness numbers of the requests ``driver`` answered."""
    from bench import check, drive, reference
    config = driver.config
    ref = reference.Reference(config, driver.grid)
    if not driver.done:
        numbers = check.Numbers()
        numbers.put("requests_answered", 0, -1)
    elif driver.mode == "sweep":
        numbers = check.check_sweeps(ref, driver.done, seed, stand_in)
    elif driver.mode == "frontier":
        # the last sweep again, with full records, through the same entry
        full = drive.Driver(config, {**driver.mix, "mode": "sweep"},
                            os.path.join(workdir, "full"))
        last = full.serve(driver.done[-1]["req"])
        numbers = check.check_frontiers(
            ref, driver.done, seed, config["objectives"],
            check.read_jsonl(os.path.join(last["out_dir"], "results.jsonl")),
            stand_in)
    else:
        numbers = check.check_queries(ref, driver.done, seed, driver.slo,
                                      stand_in)
    return numbers


def measure(args, cell: Dict, t_start: float, out=sys.stdout) -> Dict:
    import jax

    from bench import drive, generator
    from bench import trace as btrace
    from repro import devices

    bench = manifest.load()
    config = manifest.config(cell["config"])
    mix = manifest.traffic(cell["traffic"])
    devices.enable_compilation_cache()
    workdir = os.path.join(WORKDIR, cell["name"])
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    reqs = generator.Requests(mix, args.seed)
    driver = drive.Driver(config, mix, workdir)

    k0 = _counters()
    driver.warmup(reqs.warmup())
    k1 = _counters()
    trace_dir = os.path.join(workdir, "trace")
    if args.trace:
        jax.profiler.start_trace(trace_dir, profiler_options=_trace_options())
    setup_s = time.time() - t_start
    attempted, failed, window_s = serve_window(driver, reqs, args.seconds)
    if args.trace:
        jax.profiler.stop_trace()
    k2 = _counters()
    in_window = _delta(k1, k2)
    print(f"window: {attempted} requests, {failed} failed, "
          f"{window_s:.3f} s; compiled-store misses in the window "
          f"{int(in_window['misses'])}, compile seconds "
          f"{in_window['compile_seconds']:.3f}", file=out, flush=True)
    used = jax.devices()[:cell["chips"]]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in used)

    numbers = check_run(driver, args.seed, workdir)
    numbers.put("requests_failed", failed, 0)

    run = Run(mode=driver.mode, points=sum(d["points"] for d in driver.done),
              window_s=window_s,
              latencies_s=[d["latency_s"] for d in driver.done
                           if "latency_s" in d],
              spans=driver.spans,
              setup={"compile_seconds": k1["compile_seconds"]
                     - k0["compile_seconds"],
                     "executables": k1["misses"] - k0["misses"]})
    dev0 = jax.devices()[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": numbers.correct and failed == 0,
              "attempted": attempted, "failed": failed}
    metrics = {}
    if args.trace:
        path = btrace.find(trace_dir)
        run.summary = btrace.reduce(btrace.load(path)) if path else None
        if run.summary is None:
            raise RuntimeError("the trace holds no device operation inside "
                               "the window")
        device["busy_s"] = run.summary.busy_s
        device["window_s"] = run.summary.window_s
        for m in manifest.per_layer(bench, cell["name"]):
            v = manifest.reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = {
            "device_ops": run.summary.top(run.summary.op_s),
            "idle_gaps": run.summary.top(run.summary.idle_s)}
    else:
        for m in manifest.end_to_end(bench, cell["name"]):
            metrics[m["name"]] = {"value": END_TO_END[m["name"]](run, setup_s),
                                  "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    result["check"] = numbers.items
    shutil.rmtree(workdir, ignore_errors=True)
    return result, numbers


def main(argv, t_start: float) -> int:
    args = parse(argv)
    cell = manifest.workload(manifest.load(), args.workload)
    devs, why = check_device(int(cell["chips"]))
    if devs is None:
        print(f"bench: {why}", file=sys.stderr)
        return 2
    result, numbers = measure(args, cell, t_start)
    for line in numbers.lines():
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
