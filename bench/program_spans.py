"""The program's own host spans (``repro.*``) in a traced run, on the
profiler's clock together with the device's operations: what the readers
of the producer, AGE, writer and runner metrics read.

The program marks its stages with ``jax.profiler.TraceAnnotation``:

- ``repro.runner.run``: one `SweepRunner.run` call, on the thread that
  dispatches to the device;
- ``repro.pipeline.pack``: the producer packing one superbatch (with its
  compile-ahead submission), on the producer thread or, inline, on the
  dispatching one;
- ``repro.age.generate``: AGE resolving a pack's fresh hardware rows in
  one batched `age.generate_rows` call, inside that pack (none where the
  pack brings no fresh row);
- ``repro.pipeline.wait_pack``: the dispatching thread blocked on the
  producer's queue (threaded mode);
- ``repro.pipeline.dispatch``: one superbatch handed to the device;
- ``repro.pipeline.finalize``: the writer blocking on one superbatch's
  device results, folding and committing them;
- ``repro.runner.commit``: one chunk's journal commit, or one frontier
  checkpoint, inside a finalize.

A span's parent is the span that contains it in time: on its own thread by
nesting, and across threads the one ``repro.runner.run`` whose interval
contains it (the cells serve one request at a time).

The window is the harness's ``bench.window`` span; device busy time is the
union of every device's ``XLA Ops`` and ``XLA Modules`` intervals, by the
rules of `bench.trace`; everything is clipped to the window.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import math
import os
import statistics
from typing import Dict, List, Optional, Sequence, Tuple

from bench import trace as btrace

PREFIX = "repro."
RUN = "repro.runner.run"
PACK = "repro.pipeline.pack"
AGE = "repro.age.generate"
WAIT = "repro.pipeline.wait_pack"
DISPATCH = "repro.pipeline.dispatch"
FINALIZE = "repro.pipeline.finalize"
COMMIT = "repro.runner.commit"
OUTSIDE = "outside repro spans"

Interval = Tuple[float, float]                  # (start_ns, end_ns)


@dataclasses.dataclass
class Spans:
    """The window, device busy time and the program's spans, in ns."""

    lo: float
    hi: float
    busy: List[Interval]                        # sorted, disjoint
    events: List[Tuple[str, int, float, float]]  # (name, line, start, end)

    def __post_init__(self):
        self._busy_starts = [s for s, _ in self.busy]
        self._busy_cum = [0.0]
        for s, e in self.busy:
            self._busy_cum.append(self._busy_cum[-1] + e - s)

    def of(self, names: Sequence[str], lines=None) -> List[Interval]:
        return [(s, e) for n, ln, s, e in self.events
                if n in names and (lines is None or ln in lines)]

    def covered_ns(self, names: Sequence[str], lo: float = -math.inf,
                   hi: float = math.inf) -> float:
        """Length of the union of the named spans inside [lo, hi]."""
        return sum(e - s for s, e in btrace.union(
            self.of(names), max(lo, self.lo), min(hi, self.hi)))

    def busy_before(self, t: float) -> float:
        """Device busy time in [window start, t]."""
        i = bisect.bisect_right(self._busy_starts, t)
        if i == 0:
            return 0.0
        s, e = self.busy[i - 1]
        return self._busy_cum[i - 1] + min(e, t) - s

    def idle_ns(self, a: float, b: float) -> float:
        """Device idle time in [a, b]."""
        return (b - a) - (self.busy_before(b) - self.busy_before(a))

    def run_lines(self) -> set:
        """The dispatching threads: the lines that hold a runner span."""
        return {ln for n, ln, _, _ in self.events if n == RUN}

    def runs(self) -> List[Interval]:
        return sorted(self.of((RUN,)))


def from_trace(tr: btrace.Trace,
               host: List[Tuple[str, int, float, float]]) -> Optional[Spans]:
    """Clip a parsed trace to its window; None without a window or
    without any ``repro.*`` span inside it."""
    win = [s for s in tr.spans if s[0] == btrace.WINDOW_SPAN]
    if not win:
        return None
    _, lo, hi = win[0]
    events = [(n, ln, max(s, lo), min(e, hi)) for n, ln, s, e in host
              if e > lo and s < hi]
    if not events:
        return None
    dev = [(s, e) for evs in list(tr.ops.values()) + list(tr.modules.values())
           for _, s, e in evs]
    return Spans(lo=lo, hi=hi, busy=btrace.union(dev, lo, hi),
                 events=events)


def parse(xspace: bytes) -> Optional[Spans]:
    """Read a serialized XSpace, the contents of an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    host = []
    line_no = 0
    for plane in ProfileData.from_serialized_xspace(xspace).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            line_no += 1
            host.extend((e.name, line_no, float(e.start_ns),
                         float(e.start_ns + e.duration_ns))
                        for e in line.events if e.name.startswith(PREFIX))
    return from_trace(btrace.parse(xspace), host)


@functools.lru_cache(maxsize=1)
def _load(path: str, mtime_ns: int, size: int) -> Optional[Spans]:
    with open(path, "rb") as fh:
        return parse(fh.read())


def load(run) -> Optional[Spans]:
    """The spans of the run the harness has just traced: the newest trace
    under its working directory, kept only if its window is the run's.
    Parsed once for all the readers."""
    from bench import harness
    if run.summary is None:
        return None
    path = btrace.find(harness.WORKDIR)
    if path is None:
        return None
    st = os.stat(path)
    sp = _load(path, st.st_mtime_ns, st.st_size)
    if sp is None or not math.isclose((sp.hi - sp.lo) / 1e9,
                                      run.summary.window_s, rel_tol=1e-9):
        return None
    return sp


# -- the quantities the readers report ------------------------------------
def ms_per_kpoint(sp: Spans, names: Sequence[str],
                  points: int) -> Optional[float]:
    """Union of the named spans in the window, in ms per 1,000 points."""
    if not points:
        return None
    return sp.covered_ns(names) / 1e6 / (points / 1e3)


def idle_behind_producer(sp: Spans) -> float:
    """Percent of the window in which the device runs nothing while the
    dispatching thread waits for, or itself runs, the producer."""
    lines = sp.run_lines()
    waiting = btrace.union(sp.of((WAIT, PACK), lines), sp.lo, sp.hi)
    return 100.0 * sum(sp.idle_ns(s, e) for s, e in waiting) \
        / (sp.hi - sp.lo)


def age_ms_per_run(sp: Spans) -> List[float]:
    """AGE time inside each runner span, on any thread, in ms."""
    return [sp.covered_ns((AGE,), s, e) / 1e6 for s, e in sp.runs()]


def self_ms_per_run(sp: Spans) -> List[float]:
    """Each runner span's length less the union of the other program
    spans inside it, on any thread, in ms."""
    others = tuple({n for n, _, _, _ in sp.events} - {RUN})
    return [(e - s - sp.covered_ns(others, s, e)) / 1e6
            for s, e in sp.runs()]


def median(xs: List[float]) -> Optional[float]:
    return statistics.median(xs) if xs else None


def _innermost(spans: List[Tuple[str, float, float]], segs: List[float]
               ) -> List[Optional[str]]:
    """For each elementary segment between consecutive ``segs`` edges, the
    name of the latest-started, then shortest, span covering it (spans of
    one thread nest, so that is the innermost)."""
    order = sorted(spans, key=lambda x: x[1])
    out, active, i = [], [], 0
    for a, b in zip(segs, segs[1:]):
        mid = 0.5 * (a + b)
        while i < len(order) and order[i][1] <= mid:
            active.append(order[i])
            i += 1
        active = [x for x in active if x[2] > mid]
        out.append(max(active, key=lambda x: (x[1], -x[2]))[0]
                   if active else None)
    return out


def idle_by_stage(sp: Spans) -> Dict[str, float]:
    """Device idle seconds of the window by the innermost program span on
    the dispatching thread; where that is ``wait_pack``, by the innermost
    span on the producer's thread as well."""
    lines = sp.run_lines()
    disp = [(n, s, e) for n, ln, s, e in sp.events if ln in lines]
    # a thread id can come back in a later run as another stage's thread,
    # so only the producer's own spans count on the producer's lines
    prod = [(n, s, e) for n, ln, s, e in sp.events
            if n in (PACK, AGE) and ln not in lines]
    segs = sorted({sp.lo, sp.hi} | {t for _, s, e in disp + prod
                                    for t in (s, e)})
    out: Dict[str, float] = {}
    for (a, b), d, p in zip(zip(segs, segs[1:]), _innermost(disp, segs),
                            _innermost(prod, segs)):
        label = d or OUTSIDE
        if d == WAIT:
            label = f"{WAIT} > {p or 'producer outside repro spans'}"
        out[label] = out.get(label, 0.0) + sp.idle_ns(a, b) / 1e9
    return out


if __name__ == "__main__":
    # python -m bench.program_spans <trace.xplane.pb>: the spans' numbers
    # and the idle breakdown of one traced window
    import json
    import sys
    with open(sys.argv[1], "rb") as fh:
        sp = parse(fh.read())
    print(json.dumps({"window_s": (sp.hi - sp.lo) / 1e9,
                      "idle_by_stage": idle_by_stage(sp),
                      "idle_behind_producer": idle_behind_producer(sp),
                      "age_ms_p50": median(age_ms_per_run(sp)),
                      "runner_self_ms_p50": median(self_ms_per_run(sp))},
                     indent=1))
