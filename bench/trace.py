"""From a profiler trace (``*.xplane.pb``) to the numbers the benchmark
reports: device busy time and idle share, device time per executable and
per operation, and idle gaps labelled by what the host was doing.

The reduction works on plain intervals (`Trace`), so that it can be
checked on a small recorded trace without a chip:

- the window is the host span ``bench.window`` that the harness puts
  around its measured window;
- busy time is the union, over each device's ``XLA Modules`` and ``XLA
  Ops`` lines, of the intervals in which an executable or an operation
  ran, clipped to the window and averaged over the devices that ran any
  (a chip's trace can list an executable without its operations);
- device time per executable sums each device's ``XLA Modules`` events by
  name, with the ``(id)`` suffix dropped, and per operation its ``XLA
  Ops`` events by HLO name (the text before `` = ``);
- an idle gap is a stretch of the window in which no operation ran on the
  device; it is labelled with the ``bench.*`` host span that covers its
  middle (the harness's spans inside the window follow one another), or
  ``outside bench spans``.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OUTSIDE = "outside bench spans"

Interval = Tuple[float, float]          # (start_ns, end_ns)


@dataclasses.dataclass
class Trace:
    """The parts of a trace the reduction reads, as intervals in ns."""

    ops: Dict[str, List[Tuple[str, float, float]]]      # device -> ops
    modules: Dict[str, List[Tuple[str, float, float]]]  # device -> modules
    spans: List[Tuple[str, float, float]]                # host bench spans


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` file."""
    with open(path, "rb") as fh:
        return parse(fh.read())


def parse(xspace: bytes) -> Trace:
    """Read a serialized XSpace, the contents of an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_serialized_xspace(xspace)
    ops, modules, spans = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and \
                not plane.name.startswith("/device:CUSTOM"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.setdefault(plane.name, []).extend(
                        (n.split(" = ")[0], a, b) for n, a, b in _events(line))
                elif line.name == "XLA Modules":
                    modules.setdefault(plane.name, []).extend(_events(line))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(e for e in _events(line)
                             if e[0].startswith(SPAN_PREFIX))
    return Trace(ops=ops, modules=modules, spans=spans)


def find(trace_dir: str) -> Optional[str]:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def union(intervals: Sequence[Interval], lo: float, hi: float
          ) -> List[Interval]:
    """Disjoint sorted union of ``intervals`` clipped to [lo, hi]."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _base(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                       # averaged over the devices used
    devices: int
    module_s: Dict[str, float]          # per executable, summed over devices
    op_s: Dict[str, float]              # per operation, summed over devices
    idle_s: Dict[str, float]            # idle time by host span label

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def top(self, table: Dict[str, float], n: int = 10) -> List[list]:
        return [[k, v] for k, v in
                sorted(table.items(), key=lambda kv: -kv[1])[:n]]


def reduce(tr: Trace) -> Optional[Summary]:
    """None where the trace holds no window or no device operation."""
    win = [s for s in tr.spans if s[0] == WINDOW_SPAN]
    if not win:
        return None
    _, lo, hi = win[0]
    devs = set(tr.ops) | set(tr.modules)
    used = {d: tr.ops.get(d, []) + tr.modules.get(d, []) for d in devs}
    used = {d: ev for d, ev in used.items()
            if any(e > lo and s < hi for _, s, e in ev)}
    if not used or hi <= lo:
        return None
    busy_s, op_s, module_s, idle_s = 0.0, {}, {}, {}
    # the harness's spans inside the window follow one another
    inner = sorted((s for s in tr.spans if s[0] != WINDOW_SPAN),
                   key=lambda s: s[1])
    starts = [s for _, s, _ in inner]
    for dev, ev in used.items():
        busy = union([(s, e) for _, s, e in ev], lo, hi)
        busy_s += sum(e - s for s, e in busy) / 1e9
        for name, s, e in tr.ops.get(dev, ()):
            d = max(0.0, min(e, hi) - max(s, lo))
            if d > 0:
                op_s[name] = op_s.get(name, 0.0) + d / 1e9
        for name, s, e in tr.modules.get(dev, ()):
            d = max(0.0, min(e, hi) - max(s, lo))
            if d > 0:
                module_s[_base(name)] = module_s.get(_base(name), 0.0) + d / 1e9
        for s, e in gaps(busy, lo, hi):
            mid = 0.5 * (s + e)
            i = bisect.bisect_right(starts, mid) - 1
            label = inner[i][0] if i >= 0 and mid <= inner[i][2] \
                else OUTSIDE
            idle_s[label] = idle_s.get(label, 0.0) + (e - s) / 1e9 / len(used)
    return Summary(window_s=(hi - lo) / 1e9, busy_s=busy_s / len(used),
                   devices=len(used), module_s=module_s, op_s=op_s,
                   idle_s=idle_s)
