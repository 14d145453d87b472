"""Compile-ahead service + cross-design bucketed dispatch (ISSUE-10).

Covers: the configurable compiled-store size with pin-aware eviction
(AOT-queued entries must never be popped between build and first
dispatch), compile/stall wall-time accounting, the AOT service's
fleet-wide dedupe, jaxpr canonicalization collapsing sibling designs
into one bucket, bucketed-vs-unbucketed record parity across the train /
serving / serving-traffic grids (including infeasible and SLO-wall
rows), cross-backend (serial vs pipeline vs 2-worker fabric) BIT parity
with bucketing on, CLI arg validation, and resume neutrality of the new
execution-only knobs.
"""

import collections
import dataclasses
import itertools
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import compileahead, pathfinder, sweepfabric, sweeprunner
from repro.core.sweeprunner import SweepRunner, SweepSpec

ARCH = "qwen1.5-0.5b"

SPEC = SweepSpec(arches=(ARCH,), mesh_shapes=((2, 2), (4, 4)),
                 scenario="train", logic_nodes=("N7", "N5"),
                 budget_scales=(0.9, 1.0, 1.1), n_tilings=4, chunk_size=4)

# 2x2 is KV-capacity-infeasible, 4x4 feasible: parity must cover the
# non-finite masking path
SERVING_SPEC = SweepSpec(arches=(ARCH,), mesh_shapes=((2, 2), (4, 4)),
                         scenario="serving", logic_nodes=("N7",),
                         budget_scales=(0.8, 1.0), n_tilings=4,
                         chunk_size=3)

# the slo_ttft_p99 axis spans an unmeetable and a trivially-met wall, so
# the grid carries feasible, infeasible, AND SLO-wall-failing rows
TRAFFIC_SPEC = SweepSpec(arches=(ARCH,), mesh_shapes=((2, 2), (4, 4)),
                         scenario="serving-traffic", n_tilings=2,
                         chunk_size=3,
                         scenario_params={"qps": 0.1,
                                          "slo_ttft_p99": [1.0, 1e6]})

_UNIQ = itertools.count()


def _ukey(tag: str) -> tuple:
    return ("test-compileahead", tag, next(_UNIQ))


def _build(n: float):
    return lambda: jax.jit(lambda x: x * np.float32(n))


def _assert_records_match(got, want, rtol=1e-5):
    got = {r["key"]: r for r in got}
    want = {r["key"]: r for r in want}
    assert got.keys() == want.keys()
    for k, w in want.items():
        g = got[k]
        assert g.keys() == w.keys(), k
        for f, wv in w.items():
            gv = g[f]
            if isinstance(wv, float) and np.isfinite(wv):
                np.testing.assert_allclose(gv, wv, rtol=rtol,
                                           err_msg=f"{k}:{f}")
            else:
                assert gv == wv, (k, f, gv, wv)


def _assert_records_bitwise(got, want):
    got = {r["key"]: r for r in got}
    want = {r["key"]: r for r in want}
    assert got.keys() == want.keys()
    for k, w in want.items():
        g = got[k]
        assert g.keys() == w.keys(), k
        for f, wv in w.items():
            gv = g[f]
            if isinstance(wv, float) and isinstance(gv, float) \
                    and math.isnan(wv) and math.isnan(gv):
                continue
            assert gv == wv, (k, f, gv, wv)


# --------------------------------------------------------- store + eviction
def test_set_compiled_maxsize_validates_and_returns_previous():
    prev = pathfinder.compiled_maxsize()
    with pytest.raises(ValueError):
        pathfinder.set_compiled_maxsize(0)
    with pytest.raises(ValueError):
        pathfinder.set_compiled_maxsize(-3)
    assert pathfinder.compiled_maxsize() == prev
    got = pathfinder.set_compiled_maxsize(prev + 1)
    assert got == prev
    assert pathfinder.set_compiled_maxsize(prev) == prev + 1


def test_eviction_never_pops_pinned_entries_maxsize2():
    """ISSUE-10 regression: with maxsize=2, an entry the AOT service has
    pinned (queued/in-flight) survives any number of later inserts; once
    unpinned it becomes ordinary LRU fodder again."""
    saved = collections.OrderedDict(pathfinder._COMPILED)
    prev = pathfinder.compiled_maxsize()
    pathfinder._COMPILED.clear()
    try:
        pathfinder.set_compiled_maxsize(2)
        keep = _ukey("pinned")
        pathfinder.compiled_entry(keep, _build(1.0))
        pathfinder.pin_compiled(keep)
        for i in range(4):
            pathfinder.compiled_entry(_ukey("filler"), _build(float(i)))
        assert keep in pathfinder._COMPILED, \
            "LRU evicted a pinned (AOT-queued) entry"
        pathfinder.unpin_compiled(keep)
        pathfinder.compiled_entry(_ukey("filler"), _build(9.0))
        assert keep not in pathfinder._COMPILED
        assert len(pathfinder._COMPILED) <= 2
    finally:
        pathfinder.set_compiled_maxsize(prev)
        pathfinder._COMPILED.clear()
        pathfinder._COMPILED.update(saved)


def test_service_warm_protects_entry_until_first_dispatch():
    """An entry warmed through the service survives store pressure and
    dispatches its AOT executable without a fresh pin from the caller."""
    saved = collections.OrderedDict(pathfinder._COMPILED)
    prev = pathfinder.compiled_maxsize()
    pathfinder._COMPILED.clear()
    svc = compileahead.service()
    key = _ukey("aot")
    try:
        pathfinder.set_compiled_maxsize(2)
        arg = jax.ShapeDtypeStruct((4,), jnp.float32)
        assert svc.warm(key, _build(2.0), (arg,)) is True
        for i in range(4):
            pathfinder.compiled_entry(_ukey("filler"), _build(float(i)))
        assert svc.drain(timeout=120.0)
        assert key in pathfinder._COMPILED
        entry = pathfinder._COMPILED[key]
        assert entry.aot, "service drained but no AOT executable landed"
        out = entry(np.ones((4,), np.float32))
        np.testing.assert_array_equal(np.asarray(out),
                                      np.full((4,), 2.0, np.float32))
    finally:
        pathfinder.unpin_compiled(key)
        pathfinder.set_compiled_maxsize(prev)
        pathfinder._COMPILED.clear()
        pathfinder._COMPILED.update(saved)


def test_service_warm_dedupes_per_key_and_signature():
    svc = compileahead.service()
    key = _ukey("dedupe")
    arg = jax.ShapeDtypeStruct((8,), jnp.float32)
    try:
        assert svc.warm(key, _build(3.0), (arg,)) is True
        # queued or already compiled: either way, no second submission
        assert svc.warm(key, _build(3.0), (arg,)) is False
        assert svc.drain(timeout=120.0)
        assert svc.warm(key, _build(3.0), (arg,)) is False
        # a different input signature is a fresh compile
        other = jax.ShapeDtypeStruct((16,), jnp.float32)
        assert svc.warm(key, _build(3.0), (other,)) is True
        assert svc.drain(timeout=120.0)
    finally:
        pathfinder.unpin_compiled(key)
        pathfinder.unpin_compiled(key)


# ------------------------------------------------------- stats accounting
def test_compile_and_stall_seconds_accounting():
    key = _ukey("stats")
    entry = pathfinder.compiled_entry(key, _build(4.0))
    s0 = pathfinder.compile_cache_stats()
    assert {"hits", "misses", "compile_seconds", "stall_seconds"} <= \
        set(s0)
    # cold inline dispatch: the caller eats the compile => stall
    entry(np.ones((4,), np.float32))
    s1 = pathfinder.compile_cache_stats()
    assert s1["compile_seconds"] > s0["compile_seconds"]
    assert s1["stall_seconds"] > s0["stall_seconds"]
    # AOT-warmed signature: compile time accrues off-path, stall does not
    svc = compileahead.service()
    arg = jax.ShapeDtypeStruct((8,), jnp.float32)
    try:
        assert svc.warm(key, _build(4.0), (arg,))
        assert svc.drain(timeout=120.0)
        s2 = pathfinder.compile_cache_stats()
        assert s2["compile_seconds"] > s1["compile_seconds"]
        assert s2["stall_seconds"] == s1["stall_seconds"]
        entry(np.ones((8,), np.float32))
        s3 = pathfinder.compile_cache_stats()
        assert s3["compile_seconds"] == s2["compile_seconds"]
        assert s3["stall_seconds"] == s2["stall_seconds"]
    finally:
        pathfinder.unpin_compiled(key)


def test_compile_failure_is_raised_not_retried_lazily():
    """A compile the service could not finish surfaces at dispatch with the
    compiler's own error, every time, and nothing runs uncompiled."""
    calls = []

    def build():
        def refuse(x):
            calls.append(1)
            raise RuntimeError("compiler refused this program")
        return jax.jit(refuse)

    key = _ukey("refused")
    svc = compileahead.service()
    arg = jax.ShapeDtypeStruct((4,), jnp.float32)
    try:
        assert svc.warm(key, build, (arg,))
        assert svc.drain(timeout=120.0)
        entry = pathfinder._COMPILED[key]
        for _ in range(2):
            with pytest.raises(RuntimeError, match="compiler refused"):
                entry(np.ones((4,), np.float32))
        assert len(calls) == 1, "the failed program was traced again"
        assert not entry.aot
    finally:
        pathfinder.unpin_compiled(key)


# ------------------------------------------------------------- bucketing
def test_sibling_designs_share_one_bucket():
    def make_scalar(c):
        def scalar(x):
            return x * np.float32(c) + jnp.float32(2.0 * c)
        return lambda: scalar

    s0 = compileahead.bucket_stats()
    avals = (jax.ShapeDtypeStruct((3,), jnp.float32),)
    dv1 = compileahead.design_vector(_ukey("dv"), make_scalar(3.0), avals)
    dv2 = compileahead.design_vector(_ukey("dv"), make_scalar(5.0), avals)
    s1 = compileahead.bucket_stats()
    assert dv1.bucket is dv2.bucket, \
        "sibling designs (same structure, different constants) split"
    assert s1["designs_traced"] == s0["designs_traced"] + 2
    assert s1["buckets"] == s0["buckets"] + 1
    # both designs replay through the shared canonical jaxpr correctly
    x = np.arange(6, dtype=np.float32).reshape(2, 3)
    fn1 = compileahead.design_batch_fn(_ukey("dv"), make_scalar(3.0), avals)
    fn2 = compileahead.design_batch_fn(_ukey("dv"), make_scalar(5.0), avals)
    np.testing.assert_allclose(np.asarray(fn1(x)), x * 3.0 + 6.0,
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(fn2(x)), x * 5.0 + 10.0,
                               rtol=1e-6)


def test_design_vector_is_memoized_per_key():
    avals = (jax.ShapeDtypeStruct((2,), jnp.float32),)
    key = _ukey("memo")
    fn = lambda: (lambda x: x + jnp.float32(1.0))       # noqa: E731
    dv1 = compileahead.design_vector(key, fn, avals)
    dv2 = compileahead.design_vector(key, fn, avals)
    assert dv1 is dv2


def test_evaluate_matrix_stays_on_legacy_executables():
    """Template+matrix mode is ONE design over a big hardware batch —
    nothing to amortize across designs, and the parameterized bucket
    executable pays per-row coefficient gathers at warm runtime. It must
    never route through the bucketing layer, even with bucketing on."""
    from repro.configs.base import SHAPE_CELLS, get_config
    from repro.core import age, lmgraph, techlib
    from repro.core.age import Budgets
    from repro.core.parallelism import Strategy
    from repro.core.roofline import PPEConfig

    g = lmgraph.build_graph(get_config(ARCH), SHAPE_CELLS["train_4k"])
    st = Strategy("RC", kp1=1, kp2=2, dp=8)
    template = age.generate(techlib.make_tech_config("N7", "HBM2E"),
                            Budgets.default())
    base = pathfinder.pack_hw(template)
    rng = np.random.default_rng(0)
    hw = (base[None, :] * rng.uniform(0.85, 1.15, (32, base.shape[0]))
          ).astype(np.float32)

    ev = pathfinder.BatchedEvaluator(g, st, ppe=PPEConfig(n_tilings=4),
                                     cache=None, bucketed=True)
    s0 = compileahead.bucket_stats()
    rows = ev.evaluate_matrix(template, hw, devices=1)
    s1 = compileahead.bucket_stats()
    assert s1["designs_traced"] == s0["designs_traced"], \
        "evaluate_matrix registered a bucketed design vector"
    # and the legacy rows agree with the bucketed points path
    archs = [pathfinder.unpack_hw(template, row) for row in hw]
    np.testing.assert_allclose(ev.evaluate(archs), rows, rtol=1e-5)


# ------------------------------------------------------------ record parity
@pytest.mark.parametrize("spec,check_rows", [
    (SPEC, "none"),
    (SERVING_SPEC, "infeasible"),
    (TRAFFIC_SPEC, "slo_wall"),
], ids=["train", "serving", "serving-traffic"])
def test_bucketed_matches_unbucketed(spec, check_rows):
    bucketed = SweepRunner(spec, backend="serial", cache=None,
                           bucketing=True).run()
    legacy = SweepRunner(spec, backend="serial", cache=None,
                         bucketing=False).run()
    assert bucketed.complete and legacy.complete
    _assert_records_match(bucketed.records, legacy.records)
    feas = {r.get("feasible", True) for r in bucketed.records}
    if check_rows == "infeasible":
        assert feas == {True, False}, feas
    elif check_rows == "slo_wall":
        assert feas == {True, False}, feas
        # the 1.0s p99 TTFT wall must actually fail somewhere while the
        # 1e6 wall passes: both variants ride in the cell-id suffix
        walls = {r["cell"] for r in bucketed.records
                 if "slo_ttft_p99" in r["cell"]}
        assert len(walls) >= 2, walls


def test_cross_backend_bit_parity_serial_pipeline_fabric(tmp_path):
    """With bucketing on, every backend dispatches the SAME canonical
    executables, so records agree to the bit — the PR 6/PR 7 parity
    suites' rtol fuzz is not needed here."""
    serial = SweepRunner(SPEC, backend="serial", cache=None,
                         bucketing=True).run()
    pipe = SweepRunner(SPEC, backend="pipeline", cache=None,
                       bucketing=True).run()
    _assert_records_bitwise(pipe.records, serial.records)

    out = str(tmp_path / "fab")
    sweepfabric.init_dir(SPEC, out)
    a = sweepfabric.FabricWorker(out, worker_id="wa", ttl_s=60.0,
                                 claim_batch=1, max_chunks=1,
                                 bucketing=True).run()
    assert a.n_chunks_committed == 1
    b = sweepfabric.FabricWorker(out, worker_id="wb", ttl_s=60.0,
                                 claim_batch=2,
                                 bucketing=True).run()
    assert b.n_chunks_committed >= 1
    records, done = sweepfabric.merge_results(out)
    _assert_records_bitwise(records, serial.records)


def test_serving_bit_parity_serial_vs_pipeline():
    serial = SweepRunner(SERVING_SPEC, backend="serial", cache=None,
                         bucketing=True).run()
    pipe = SweepRunner(SERVING_SPEC, backend="pipeline", cache=None,
                       bucketing=True).run()
    _assert_records_bitwise(pipe.records, serial.records)


# ----------------------------------------------------- runstats + resume
def test_runstats_reports_compile_and_stall_seconds():
    spec = dataclasses.replace(SPEC, mesh_shapes=((8, 2),),
                               logic_nodes=("N7",), budget_scales=(1.0,),
                               chunk_size=2)
    # unbucketed + no lookahead: the lazy compile lands on-path, so both
    # counters must be visible in the per-run delta
    first = SweepRunner(spec, backend="pipeline", cache=None,
                        bucketing=False, compile_ahead=0).run()
    assert first.compile_seconds > 0.0
    assert first.stall_seconds > 0.0
    # same process, same spec: fully warm, zero compile in the delta
    second = SweepRunner(spec, backend="pipeline", cache=None,
                         bucketing=False, compile_ahead=0).run()
    assert second.compile_seconds == 0.0
    assert second.stall_seconds == 0.0
    _assert_records_match(second.records, first.records)


def test_resume_is_neutral_to_bucketing_and_compile_ahead(tmp_path):
    """The knobs are execution-only: a dir written under one setting
    resumes under the other with zero re-evaluation (unchanged chunk
    hashes + fingerprints), in both directions."""
    d1 = str(tmp_path / "a")
    first = SweepRunner(SPEC, out_dir=d1, backend="pipeline",
                        bucketing=False, compile_ahead=0).run(max_chunks=2)
    assert first.n_chunks_evaluated == 2 and not first.complete
    second = SweepRunner(SPEC, out_dir=d1, backend="pipeline",
                         bucketing=True).run(resume=True)
    assert second.n_chunks_skipped == 2 and second.complete

    d2 = str(tmp_path / "b")
    third = SweepRunner(SPEC, out_dir=d2, backend="pipeline",
                        bucketing=True, compile_ahead=2).run(max_chunks=2)
    assert third.n_chunks_evaluated == 2 and not third.complete
    fourth = SweepRunner(SPEC, out_dir=d2, backend="pipeline",
                         bucketing=False, compile_ahead=0).run(resume=True)
    assert fourth.n_chunks_skipped == 2 and fourth.complete
    keys = sorted(r["key"] for r in fourth.records)
    assert keys == sorted(lb.key()
                          for lb in sweeprunner.enumerate_labels(SPEC))


# ------------------------------------------------------------------- CLI
def test_cli_rejects_nonpositive_superbatch_and_compile_ahead(capsys):
    from repro import pathfind
    base = ["sweep", "--arch", ARCH, "--mesh", "2x2"]
    assert pathfind.main(base + ["--superbatch", "0"]) == 2
    assert "--superbatch" in capsys.readouterr().err
    assert pathfind.main(base + ["--superbatch", "-8"]) == 2
    assert "--superbatch" in capsys.readouterr().err
    assert pathfind.main(base + ["--compile-ahead", "0"]) == 2
    assert "--compile-ahead" in capsys.readouterr().err
    assert pathfind.main(base + ["--compile-ahead", "-1"]) == 2
    assert "--compile-ahead" in capsys.readouterr().err
    # the worker validates the same way, before touching --dir
    assert pathfind.main(["sweep-worker", "--dir", "/nonexistent",
                          "--superbatch", "0"]) == 2
    assert "--superbatch" in capsys.readouterr().err
    assert pathfind.main(["sweep-worker", "--dir", "/nonexistent",
                          "--compile-ahead", "-2"]) == 2
    assert "--compile-ahead" in capsys.readouterr().err


def test_cli_summary_prints_compile_seconds(tmp_path, capsys):
    from repro import pathfind
    rc = pathfind.main(["sweep", "--arch", ARCH, "--mesh", "2x2",
                        "--mesh", "4x4", "--tilings", "4",
                        "--chunk-size", "4", "--backend", "pipeline",
                        "--compile-ahead", "2",
                        "--csv", str(tmp_path / "out.csv")])
    assert rc == 0
    err = capsys.readouterr().err
    assert "# compile:" in err
    assert "stalling the eval path" in err


def test_worker_cmd_carries_compile_knobs(tmp_path):
    coord = sweepfabric.FabricCoordinator(SPEC, str(tmp_path), workers=0,
                                          compile_ahead=3, bucketing=False)
    cmd = coord.worker_cmd()
    assert cmd[cmd.index("--compile-ahead") + 1] == "3"
    assert "--no-bucketing" in cmd
    # defaults stay off the command line (workers keep their own defaults)
    coord2 = sweepfabric.FabricCoordinator(SPEC, str(tmp_path), workers=0)
    assert "--compile-ahead" not in coord2.worker_cmd()
    assert "--no-bucketing" not in coord2.worker_cmd()


def test_worker_stats_journal_reports_compile_seconds(tmp_path):
    out = str(tmp_path / "fab")
    spec = dataclasses.replace(SPEC, budget_scales=(1.0,))
    sweepfabric.init_dir(spec, out)
    sweepfabric.FabricWorker(out, worker_id="wstats", ttl_s=60.0,
                             claim_batch=2).run()
    import json
    with open(os.path.join(out, "workers", "stats.wstats.json")) as fh:
        stats = json.load(fh)
    assert "compile_seconds" in stats and "stall_seconds" in stats
    assert stats["compile_seconds"] >= 0.0
    assert stats["stall_seconds"] >= 0.0
