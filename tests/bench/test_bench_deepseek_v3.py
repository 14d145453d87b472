"""The DeepSeek-V3 cell (`query.deepseek-v3.serve`) on the CPU.

- the program's records of the cell's query grid, at two budget scales
  drawn from a seed, equal what the configuration's own reference module
  (`bench/crossflow/deepseek_v3.py`) computes, within `REF_RTOL`;
- that module imports nothing of the program;
- `lmgraph.graph_stats()` counts what `build_graph` built, by GEMM tag
  and layer group;
- ``repro.runner.prepare`` marks each run's prologue before its first
  pack, ``repro.lmgraph.build`` each graph built on a cache miss, and
  the two new readers read them;
- the default reference's snapshot is byte for byte what PR 15 wrote.
"""

import ast
import hashlib
import os

import jax
import pytest

from bench import check, drive, generator, manifest, reference
from bench import program_spans as ps
from bench import trace as btrace
from bench.crossflow import deepseek_v3
from repro.configs.base import SHAPE_CELLS, get_config
from repro.core import lmgraph, scenarios, sweeprunner
from test_bench_program_spans import (QUERY_DEVICE, QUERY_MAIN,  # noqa: E402
                                      QUERY_PRODUCER, QUERY_WRITER, _run,
                                      _xspace)

CELL = manifest.workload(manifest.load(), "query.deepseek-v3.serve")
CONFIG = manifest.config(CELL["config"])
MIX = manifest.traffic(CELL["traffic"])
GRID = CONFIG["grids"][MIX["grid"]]
PREPARE = "repro.runner.prepare"
BUILD = "repro.lmgraph.build"


def _runner(scale: float) -> sweeprunner.SweepRunner:
    spec = drive.Driver(CONFIG, MIX, "unused").spec([scale])
    return sweeprunner.SweepRunner(spec, out_dir=None, backend="pipeline")


@pytest.fixture(scope="module")
def answered():
    """{scale: the program's records} at two scales drawn from seed 16."""
    reqs = generator.Requests(MIX, 16)
    scales = [reqs.next()["scales"][0] for _ in range(2)]
    return {s: _runner(s).run().records for s in scales}


def test_program_records_equal_the_reference_modules(answered):
    ref = reference.Reference(CONFIG, GRID)
    assert type(ref.cfg) is deepseek_v3.ArchConfig
    latent = 61 * 1152 * 32768 * 128
    with jax.default_device(jax.devices("cpu")[0]):
        for scale, recs in answered.items():
            assert sorted(r["key"] for r in recs) == sorted(ref.keys([scale]))
            assert len(recs) == 6
            for r in recs:
                want = ref.record(check.label_of_key(r["key"]))
                diff, wrong = reference.compare_records(r, want)
                assert diff <= reference.REF_RTOL and wrong == 0, r["key"]
                dp = int(r["mesh"].split("x")[0])
                assert r["kv_bytes_per_device"] == pytest.approx(latent / dp)


def test_reference_module_imports_nothing_of_the_program():
    with open(deepseek_v3.__file__) as fh:
        tree = ast.parse(fh.read())
    names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names]
    names += [n.module for n in ast.walk(tree)
              if isinstance(n, ast.ImportFrom) and n.module]
    assert names and not any(n.split(".")[0] == "repro" for n in names)


def test_graph_stats_count_what_build_graph_built():
    before = lmgraph.graph_stats()["built"]
    lmgraph.build_graph(get_config("deepseek-v3"), SHAPE_CELLS["decode_32k"])
    lmgraph.build_graph(get_config("deepseek-v3"), SHAPE_CELLS["prefill_32k"])
    lmgraph.build_graph(get_config("mistral-large-123b"),
                        SHAPE_CELLS["train_4k"])
    stats = lmgraph.graph_stats()
    assert stats["built"] == before + 3
    graphs = stats["graphs"]
    # per MLA group: q_a, q_b, kv_a, then q_absorb, qk, av, v_up (decode)
    # or kv_b, qk, av (prefill), and o; other: router, shared up and
    # down, lm_head
    assert graphs["deepseek-v3|decode_32k"] == {
        "gemm": {"mla": 16, "moe": 2, "dense": 2, "other": 4},
        "groups": 2, "repeat": 61}
    assert graphs["deepseek-v3|prefill_32k"] == {
        "gemm": {"mla": 14, "moe": 2, "dense": 2, "other": 4},
        "groups": 2, "repeat": 61}
    mistral = graphs["mistral-large-123b|train_4k"]
    assert mistral["groups"] == 1 and mistral["repeat"] == 88
    assert mistral["gemm"]["mla"] == mistral["gemm"]["moe"] \
        == mistral["gemm"]["dense"] == 0


def test_prepare_and_graph_build_spans(tmp_path, monkeypatch, answered):
    monkeypatch.setattr(scenarios, "_GRAPH_CACHE", {})   # graphs built anew
    runner = _runner(next(iter(answered)))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            runner.run()
    finally:
        jax.profiler.stop_trace()
    with open(btrace.find(str(tmp_path)), "rb") as fh:
        sp = ps.parse(fh.read())
    (lo, hi), = sp.of((ps.RUN,))
    (p0, p1), = sp.of((PREPARE,))
    packs = sp.of((ps.PACK,))
    assert lo <= p0 <= p1 <= min(s for s, _ in packs)
    builds = sp.of((BUILD,))
    assert len(builds) == 2                      # prefill_32k, decode_32k
    assert all(any(a <= s <= e <= b for a, b in packs) for s, e in builds)


def test_prepare_and_device_readers(tmp_path, monkeypatch):
    main = (QUERY_MAIN[0], QUERY_MAIN[1] + [
        (PREPARE, 10.5, 11), (PREPARE, 50.5, 51.5), (PREPARE, 100.5, 100.75)])
    run = _run(tmp_path / "a", monkeypatch,
               _xspace(QUERY_DEVICE, [main, QUERY_PRODUCER, QUERY_WRITER]),
               mode="query", points=18)
    run.latencies_s = [0.03, 0.04, 0.02]
    read = lambda name: manifest.reader(name)(run)   # noqa: E731
    assert read("query.prepare_ms_p50") == pytest.approx(0.5)
    # jit_scalar runs 0.3 and 0.6 ms on the device for three queries (the
    # hand-built trace rounds those ends to whole picoseconds)
    assert read("query.eval_device_ms_per_query") \
        == pytest.approx(0.3, rel=1e-5)
    # the parent program marks no prologue; a sweep run is no query
    parent = _run(tmp_path / "b", monkeypatch,
                  _xspace(QUERY_DEVICE, [QUERY_MAIN, QUERY_PRODUCER,
                                         QUERY_WRITER]), mode="query")
    assert manifest.reader("query.prepare_ms_p50")(parent) is None
    run.mode = "sweep"
    assert read("query.eval_device_ms_per_query") is None


def test_default_reference_snapshot_is_unchanged():
    path = os.path.join(os.path.dirname(__file__), "data",
                        "reference_records.json")
    with open(path, "rb") as fh:
        digest = hashlib.sha256(fh.read()).hexdigest()
    assert digest == ("a8a699d5184edabd2799caa6aa0bab2c"
                      "def42400c43a99e223461baa943d55e2")
