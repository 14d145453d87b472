"""The sweep pipeline's host spans (``repro.*``) under the profiler.

A tiny sweep whose budget scales no other test uses (so the hardware and
packed-row caches miss) is traced and its spans read back through
`bench.program_spans`: one runner span per call, one pack, dispatch and
finalize per superbatch, one AGE span per superbatch that brings fresh
hardware rows (they are resolved in one batched call), one commit
per chunk (per checkpoint in frontier mode), every stage span inside the
runner span, and records identical to an untraced run of the same spec.
"""

import dataclasses
import glob
import os
import sys

import jax
import pytest

from repro.core import sweeppipeline
from repro.core.sweeprunner import SweepRunner, SweepSpec, enumerate_labels

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import program_spans as ps  # noqa: E402

SPEC = SweepSpec(arches=("qwen1.5-0.5b",), mesh_shapes=((2, 2), (4, 4)),
                 scenario="train", logic_nodes=("N7", "N5"),
                 budget_scales=(0.61237, 0.61241), n_tilings=4, chunk_size=2)
SUPERBATCH = 4                          # two chunks per superbatch
STAGES = (ps.PACK, ps.AGE, ps.WAIT, ps.DISPATCH, ps.FINALIZE, ps.COMMIT)


def _traced(tmp_path, run):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path / "trace"),
                             profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            st = run()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"),
                      recursive=True)
    with open(path, "rb") as fh:
        return st, ps.parse(fh.read())


@pytest.mark.parametrize("frontier", [False, True],
                         ids=["records", "frontier"])
@pytest.mark.parametrize("threads", [True, False],
                         ids=["threaded", "inline"])
def test_spans_per_stage(tmp_path, monkeypatch, threads, frontier):
    init = sweeppipeline.PipelineExecutor.__init__

    def with_threads(self, *args, **kw):
        init(self, *args, **dict(kw, threads=threads))
    monkeypatch.setattr(sweeppipeline.PipelineExecutor, "__init__",
                        with_threads)
    # fresh scales per case, so every hardware row is built here
    k = 2 * threads + frontier
    spec = dataclasses.replace(SPEC, budget_scales=tuple(
        s + 1e-5 * k for s in SPEC.budget_scales))
    labels = enumerate_labels(spec)
    chunks = -(-len(labels) // spec.chunk_size)
    packs = -(-chunks // (SUPERBATCH // spec.chunk_size))
    seen, fresh_packs = set(), 0        # packs with rows no earlier had
    for i in range(0, len(labels), SUPERBATCH):
        rows = {(lb.logic, lb.hbm, lb.net, lb.scale)
                for lb in labels[i:i + SUPERBATCH]}
        fresh_packs += bool(rows - seen)
        seen |= rows

    def run(out):
        return SweepRunner(spec, out_dir=str(tmp_path / out),
                           backend="pipeline", cache=None,
                           superbatch=SUPERBATCH).run(frontier_only=frontier)
    st, sp = _traced(tmp_path, lambda: run("traced"))
    plain = run("plain")

    count = {n: sum(e[0] == n for e in sp.events)
             for n in (ps.RUN,) + STAGES}
    assert count[ps.RUN] == 1
    assert count[ps.PACK] == count[ps.DISPATCH] == count[ps.FINALIZE] \
        == packs >= 2
    assert count[ps.AGE] == fresh_packs >= 1
    assert count[ps.COMMIT] == (packs if frontier else chunks)
    assert (count[ps.WAIT] > 0) == threads

    (_, run_line, lo, hi), = [e for e in sp.events if e[0] == ps.RUN]
    for name, line, s, e in sp.events:
        assert lo <= s <= e <= hi, name
        if name in (ps.WAIT, ps.DISPATCH):
            assert line == run_line, name
        if name == ps.PACK:
            assert (line == run_line) == (not threads)
    assert sp.run_lines() == {run_line}

    assert st.n_points_evaluated == plain.n_points_evaluated == len(labels)
    key = lambda r: r["key"]                            # noqa: E731
    assert sorted(st.records, key=key) == sorted(plain.records, key=key)
