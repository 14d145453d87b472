"""What a run asks of the program: user sweeps, frontier sweeps and sizing
queries through its public entries.

Only `sweeprunner.SweepSpec`, `SweepRunner.run` (pipeline backend),
`traffic.split_params` and `traffic.size_fleet` are called.  Every call is
wrapped in a `jax.profiler.TraceAnnotation` named after what the host is
doing, so that a traced run can attribute device idle time to it.
"""

from __future__ import annotations

import os
import shutil
import time
from typing import Dict, List, Mapping

import jax

from bench.check import plan_summary
from repro.core import sweeprunner, traffic

SPAN_WARMUP = "bench.warmup"
SPAN_SWEEP = "bench.sweep"
SPAN_QUERY_EVAL = "bench.query.eval"
SPAN_QUERY_SIZE = "bench.query.size"


class Driver:
    """Drives one cell: its configuration's grid under its traffic mix."""

    def __init__(self, config: Mapping, mix: Mapping, workdir: str):
        self.config = config
        self.mix = mix
        self.mode = mix["mode"]
        self.grid = config["grids"][mix["grid"]]
        self.workdir = workdir
        self.done: List[Dict] = []       # the window's requests and answers
        self.spans: Dict[str, List[float]] = {}
        self._n = 0
        params = dict(self.grid.get("scenario_params") or {})
        self.slo = {k: float(v) for k, v in (mix.get("slo") or {}).items()}
        if self.mode == "query":
            tm, pol, _ = traffic.split_params(
                {k: v for k, v in params.items() if not isinstance(v, list)})
            self._tm, self._pol = tm, pol

    def spec(self, scales) -> sweeprunner.SweepSpec:
        g = self.grid
        params = g.get("scenario_params") or None
        return sweeprunner.SweepSpec(
            arches=(self.config["program_arch"],),
            mesh_shapes=tuple(tuple(m) for m in g["meshes"]),
            scenario=self.config["scenario"],
            cells=tuple(self.config["cells"]),
            logic_nodes=tuple(g["logic"]), hbms=tuple(g["hbm"]),
            nets=tuple(g["net"]),
            budget_scales=tuple(float(s) for s in scales),
            n_tilings=int(self.config["n_tilings"]),
            scenario_params=dict(params) if params else None)

    def _span(self, name: str, seconds: float) -> None:
        self.spans.setdefault(name, []).append(seconds)

    def _out_dir(self) -> str:
        self._n += 1
        d = os.path.join(self.workdir, f"sweep{self._n:05d}")
        shutil.rmtree(d, ignore_errors=True)
        return d

    # -- one request -------------------------------------------------------
    def serve(self, req: Mapping, warm: bool = False) -> Dict:
        """Answer one request; returns what the check needs of it."""
        if self.mode == "query":
            return self._query(req, warm)
        span = SPAN_WARMUP if warm else SPAN_SWEEP
        out_dir = self._out_dir()
        runner = sweeprunner.SweepRunner(self.spec(req["scales"]),
                                         out_dir=out_dir, backend="pipeline")
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(span):
            if self.mode == "frontier":
                st = runner.run(frontier_only=True)
            else:
                st = runner.run(collect=False)
        self._span(span, time.perf_counter() - t0)
        return {"req": dict(req), "out_dir": out_dir,
                "points": int(st.n_points_evaluated),
                "overflowed": int(st.n_frontier_overflowed),
                "frontier": st.records if self.mode == "frontier" else None}

    def _query(self, req: Mapping, warm: bool) -> Dict:
        runner = sweeprunner.SweepRunner(self.spec(req["scales"]),
                                         out_dir=None, backend="pipeline")
        eval_span = SPAN_WARMUP if warm else SPAN_QUERY_EVAL
        size_span = SPAN_WARMUP if warm else SPAN_QUERY_SIZE
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(eval_span):
            st = runner.run()
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation(size_span):
            plan = traffic.size_fleet(st.records, req["qps"], slo=self.slo,
                                      traffic=self._tm, policy=self._pol)
        t2 = time.perf_counter()
        self._span(eval_span, t1 - t0)
        self._span(size_span, t2 - t1)
        return {"req": dict(req), "points": int(st.n_points_evaluated),
                "records": st.records, "latency_s": t2 - t0,
                "plan": plan_summary(plan)}

    # -- set-up and window -------------------------------------------------
    def warmup(self, reqs) -> None:
        for req in reqs:
            self.serve(req, warm=True)
