"""Pipelined sweep executor tests (ISSUE-5 tentpole).

Covers: pipeline-vs-serial record parity (train and serving, including
after an interrupted sweep resumes across backends), the device-resident
streaming frontier (fused Pareto reduction == full materialization, tie
and overflow semantics of `frontier_merge`), resume-identity stability
(PR4-era fingerprints and checkpoints), call-time prediction-cache
resolution, and the cache/compile hit-miss accounting on `RunStats`.
"""

import collections
import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import pathfinder, scenarios, sweeprunner
from repro.core.sweeprunner import SweepRunner, SweepSpec

SPEC = SweepSpec(arches=("qwen1.5-0.5b",), mesh_shapes=((2, 2), (4, 4)),
                 scenario="train", logic_nodes=("N7", "N5"),
                 budget_scales=(0.9, 1.0, 1.1), n_tilings=4, chunk_size=4)

# meshes chosen so the grid spans infeasible (KV cache does not fit on
# 2x2) AND feasible points — the parity/frontier tests must exercise the
# non-finite masking path
SERVING_SPEC = SweepSpec(arches=("qwen1.5-0.5b",),
                         mesh_shapes=((2, 2), (4, 4)), scenario="serving",
                         logic_nodes=("N7",), budget_scales=(0.8, 1.0),
                         n_tilings=4, chunk_size=3)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _by_key(records):
    return {r["key"]: r for r in records}


def _assert_records_match(got, want):
    got, want = _by_key(got), _by_key(want)
    assert got.keys() == want.keys()
    for k, w in want.items():
        g = got[k]
        assert g.keys() == w.keys(), k
        for f, wv in w.items():
            gv = g[f]
            if isinstance(wv, float) and np.isfinite(wv):
                np.testing.assert_allclose(gv, wv, rtol=1e-5,
                                           err_msg=f"{k}:{f}")
            else:
                assert gv == wv, (k, f, gv, wv)


# ------------------------------------------------------------ record parity
def test_pipeline_matches_serial_train(tmp_path):
    serial = SweepRunner(SPEC, out_dir=str(tmp_path / "s"),
                         backend="serial", cache=None).run()
    pipe = SweepRunner(SPEC, out_dir=str(tmp_path / "p"),
                       backend="pipeline", cache=None).run()
    assert pipe.complete and pipe.n_points_evaluated == \
        serial.n_points_evaluated
    _assert_records_match(pipe.records, serial.records)


def test_pipeline_matches_serial_serving(tmp_path):
    serial = SweepRunner(SERVING_SPEC, out_dir=str(tmp_path / "s"),
                         backend="serial", cache=None).run()
    pipe = SweepRunner(SERVING_SPEC, out_dir=str(tmp_path / "p"),
                       backend="pipeline", cache=None).run()
    _assert_records_match(pipe.records, serial.records)
    # the reference grid must exercise both feasible and infeasible points
    feas = {r["feasible"] for r in serial.records}
    assert feas == {True, False}, feas


def test_pipeline_resumes_serial_checkpoints_with_zero_reeval(tmp_path):
    """A PR4-era checkpoint dir (written by the synchronous serial
    backend) resumes under the pipeline executor: zero re-evaluation,
    identical point set, unchanged fingerprint for profile-less specs."""
    first = SweepRunner(SPEC, out_dir=str(tmp_path),
                        backend="serial").run(max_chunks=2)
    assert first.n_chunks_evaluated == 2 and not first.complete
    second = SweepRunner(SPEC, out_dir=str(tmp_path),
                         backend="pipeline").run(resume=True)
    assert second.n_chunks_skipped == 2
    assert second.complete
    keys = sorted(r["key"] for r in second.records)
    assert keys == sorted(lb.key()
                          for lb in sweeprunner.enumerate_labels(SPEC))


def test_fingerprint_pinned_for_profile_less_specs():
    """Resume identity: the PR4-era fingerprint of a profile-less spec
    must never change (old checkpoint dirs must keep resuming)."""
    spec = SweepSpec(arches=("qwen1.5-0.5b",),
                     mesh_shapes=((2, 2), (4, 4)), scenario="train",
                     logic_nodes=("N7", "N5"), n_tilings=4, chunk_size=1)
    assert spec.fingerprint() == "fadd310e03f4106b"


def test_pick_backend_auto_is_pipeline():
    assert sweeprunner.pick_backend("auto") == "pipeline"
    assert sweeprunner.pick_backend("serial") == "serial"


# --------------------------------------------------------- frontier mode
def test_frontier_only_matches_full_materialization(tmp_path):
    for spec in (SPEC, SERVING_SPEC):
        scn = scenarios.get_scenario(spec.scenario)
        full = SweepRunner(spec, backend="pipeline", cache=None).run()
        want = sweeprunner.pareto_records(full.records, scn.objectives)
        assert want, "reference frontier must be non-empty"
        front = SweepRunner(spec, backend="pipeline", cache=None,
                            out_dir=str(tmp_path / spec.scenario)).run(
            frontier_only=True)
        assert front.frontier_only
        assert front.n_frontier_overflowed == 0
        assert front.n_points_evaluated == full.n_points_evaluated
        _assert_records_match(front.records, want)
        # frontier.jsonl holds exactly the frontier
        path = tmp_path / spec.scenario / "frontier.jsonl"
        rows = [json.loads(ln) for ln in
                path.read_text().strip().splitlines()]
        assert sorted(r["key"] for r in rows) == \
            sorted(r["key"] for r in want)


def test_frontier_only_resumes_carried_state(tmp_path):
    """ISSUE-6 satellite: an interrupted frontier-only sweep resumes from
    DIR/frontier_state.npz with zero re-evaluation and reaches the same
    frontier as an uninterrupted run."""
    d = str(tmp_path / "front")
    part = SweepRunner(SPEC, out_dir=d, backend="pipeline",
                       cache=None).run(frontier_only=True, max_chunks=2)
    assert not part.complete
    assert os.path.exists(os.path.join(d, "frontier_state.npz"))
    done = SweepRunner(SPEC, out_dir=d, backend="pipeline",
                       cache=None).run(frontier_only=True, resume=True)
    assert done.complete
    assert done.n_chunks_skipped == 2
    assert done.n_points_evaluated == part.n_points_total - \
        part.n_points_evaluated
    fresh = SweepRunner(SPEC, out_dir=str(tmp_path / "fresh"),
                        backend="pipeline", cache=None).run(
        frontier_only=True)
    _assert_records_match(done.records, fresh.records)
    # a fully-resumed frontier re-evaluates nothing at all
    again = SweepRunner(SPEC, out_dir=d, backend="pipeline",
                        cache=None).run(frontier_only=True, resume=True)
    assert again.n_points_evaluated == 0
    _assert_records_match(again.records, fresh.records)


def test_frontier_resume_guards(tmp_path):
    d = str(tmp_path / "front")
    SweepRunner(SPEC, out_dir=d, backend="pipeline",
                cache=None).run(frontier_only=True, max_chunks=1)
    # a second non-resume run must not silently merge into stale state
    with pytest.raises(FileExistsError, match="frontier-state"):
        SweepRunner(SPEC, out_dir=d, backend="pipeline",
                    cache=None).run(frontier_only=True)
    # capacity changes the carried-state shape: refuse, don't corrupt
    with pytest.raises(ValueError, match="capacity"):
        SweepRunner(SPEC, out_dir=d, backend="pipeline", cache=None).run(
            frontier_only=True, resume=True, frontier_capacity=16)
    # a different spec cannot adopt the state
    other = dataclasses.replace(SPEC, budget_scales=(1.0,))
    with pytest.raises(ValueError, match="spec changed"):
        SweepRunner(other, out_dir=d, backend="pipeline", cache=None).run(
            frontier_only=True, resume=True)


def test_frontier_merge_dominance_ties_and_overflow():
    state = pathfinder.frontier_init(4, 2, 1)
    vals = jnp.asarray([[1.0, 5.0], [1.0, 5.0],    # exact tie pair
                        [5.0, 1.0], [4.0, 4.0],    # (4,4) dominated later
                        [3.0, 3.0], [np.inf, 0.0]])
    payload = jnp.arange(6, dtype=jnp.float32)[:, None]
    idx = jnp.asarray([0, 1, 2, 3, 4, -1], dtype=jnp.int32)
    state = pathfinder.frontier_merge(state, vals, payload, idx)
    out_vals, out_pay, out_idx, over = pathfinder.frontier_unpack(state)
    # ties both kept; dominated (4,4) dropped; non-finite/padding excluded
    assert sorted(out_idx.tolist()) == [0, 1, 2, 4]
    assert over == 0
    # a later batch can evict carried points it dominates
    state = pathfinder.frontier_merge(
        state, jnp.asarray([[0.5, 0.5]]),
        jnp.asarray([[9.0]]), jnp.asarray([7], dtype=jnp.int32))
    _, _, out_idx, over = pathfinder.frontier_unpack(state)
    assert out_idx.tolist() == [7]
    assert over == 0


def test_frontier_merge_overflow_counted():
    state = pathfinder.frontier_init(2, 2, 1)
    # 4 mutually non-dominated points into capacity 2
    vals = jnp.asarray([[1.0, 4.0], [2.0, 3.0], [3.0, 2.0], [4.0, 1.0]])
    payload = jnp.zeros((4, 1), dtype=jnp.float32)
    idx = jnp.asarray([0, 1, 2, 3], dtype=jnp.int32)
    state = pathfinder.frontier_merge(state, vals, payload, idx)
    out_vals, _, out_idx, over = pathfinder.frontier_unpack(state)
    assert over == 2
    assert out_idx.tolist() == [0, 1]          # lowest first objective


# ------------------------------------------------- cache + compile stats
def test_eval_labels_resolves_cache_at_call_time():
    """Regression (ISSUE-5 satellite): replacing the process-wide
    prediction cache must take effect for default-arg callers — the old
    `cache=pathfinder.prediction_cache()` default froze the singleton at
    import time."""
    old = pathfinder.prediction_cache()
    fresh = pathfinder.PredictionCache()
    pathfinder.set_prediction_cache(fresh)
    try:
        labels = sweeprunner.enumerate_labels(SPEC)[:2]
        with pytest.warns(DeprecationWarning, match="eval_labels"):
            sweeprunner.eval_labels(SPEC, labels)
        stats = fresh.stats
        assert stats["hits"] + stats["misses"] > 0, (
            "replacement cache saw no traffic: eval_labels is still "
            "bound to the import-time singleton")
    finally:
        pathfinder.set_prediction_cache(old)


def test_runstats_reports_cache_and_compile_counters(tmp_path):
    pathfinder.clear_prediction_cache()
    spec = dataclasses.replace(SPEC, budget_scales=(1.0,))
    n = len(sweeprunner.enumerate_labels(spec))
    first = SweepRunner(spec, out_dir=str(tmp_path / "a"),
                        backend="pipeline").run()
    assert first.cache_misses >= n
    # identical spec, fresh dir, same process: every point is a hit
    second = SweepRunner(spec, out_dir=str(tmp_path / "b"),
                         backend="pipeline").run()
    assert second.cache_hits >= n
    assert second.cache_misses == 0
    _assert_records_match(second.records, first.records)
    # a fresh (empty) cache re-evaluates but REUSES the compiled fns
    third = SweepRunner(spec, out_dir=str(tmp_path / "c"),
                        backend="pipeline",
                        cache=pathfinder.PredictionCache()).run()
    assert third.cache_misses >= n
    assert third.compile_hits > 0 and third.compile_misses == 0
    # resumed completed sweep: 100% chunk-skip, nothing evaluated
    resumed = SweepRunner(spec, out_dir=str(tmp_path / "a"),
                          backend="pipeline").run(resume=True)
    assert resumed.n_chunks_skipped == resumed.n_chunks_total
    assert resumed.n_chunks_evaluated == 0
    assert resumed.n_points_evaluated == 0


def test_fresh_hardware_rows_resolve_in_one_age_call_per_pack(
        tmp_path, monkeypatch):
    """Each superbatch's fresh hardware rows go through one batched AGE
    call (no per-scalar eager rows), a rerun on the same scales makes
    none, and the records equal those of a sweep whose rows come from
    eager `age.generate` + `pack_hw`, label by label."""
    from repro.core import age, sweeppipeline
    # scales no other test uses; 2-label superbatches, so the first two
    # packs each bring two of the 4 fresh rows and the rest bring none
    spec = dataclasses.replace(SPEC, budget_scales=(0.73411, 0.73417),
                               chunk_size=2)
    labels = sweeprunner.enumerate_labels(spec)
    seen, fresh_packs = set(), 0
    for i in range(0, len(labels), 2):
        rows = {(lb.logic, lb.hbm, lb.net, lb.scale)
                for lb in labels[i:i + 2]}
        fresh_packs += bool(rows - seen)
        seen |= rows
    assert len(labels) // 2 > fresh_packs == 2 and len(seen) == 4

    def run(out):
        return SweepRunner(spec, out_dir=str(tmp_path / out),
                           backend="pipeline", cache=None,
                           superbatch=2).run()

    def fresh_caches():
        monkeypatch.setattr(sweeprunner, "_HW_CACHE", {})
        monkeypatch.setattr(sweeppipeline, "_ROW_CACHE",
                            collections.OrderedDict())

    fresh_caches()
    with monkeypatch.context() as m:
        m.setattr(age, "generate_rows", lambda techs, budgets: [
            age.generate(t, b) for t, b in zip(techs, budgets)])
        eager = run("eager")

    fresh_caches()
    s0 = age.age_stats()
    batched = run("batched")
    s1 = age.age_stats()
    again = run("again")
    s2 = age.age_stats()
    assert s1["calls"] - s0["calls"] == fresh_packs
    assert s1["rows"] - s0["rows"] == len(seen)
    assert s1["eager_rows"] == s0["eager_rows"]
    assert s2 == s1
    assert batched.n_points_evaluated == len(labels)
    for got in (batched.records, again.records):
        got, want = _by_key(got), _by_key(eager.records)
        assert got.keys() == want.keys()
        for k, w in want.items():
            for f, wv in w.items():
                if isinstance(wv, float) and np.isfinite(wv):
                    assert abs(got[k][f] - wv) <= 1e-6 * abs(wv), (k, f)
                else:
                    assert got[k][f] == wv, (k, f)


def test_cli_frontier_only_and_cache_summary(tmp_path, capsys):
    import jax

    from repro import pathfind
    prev_cc = jax.config.jax_compilation_cache_dir
    try:
        _cli_frontier_and_summary(tmp_path, capsys, pathfind)
    finally:
        # the CLI points the persistent compile cache at its fixed
        # directory; give the rest of this process its own setting back
        jax.config.update("jax_compilation_cache_dir", prev_cc)


def _cli_frontier_and_summary(tmp_path, capsys, pathfind):
    out = str(tmp_path / "sweep")
    rc = pathfind.main(["sweep", "--arch", "qwen1.5-0.5b",
                        "--mesh", "2x2", "--mesh", "4x4",
                        "--tilings", "4", "--chunk-size", "4",
                        "--backend", "pipeline", "--out", out])
    assert rc == 0
    err = capsys.readouterr().err
    assert "cache: prediction" in err and "compiled fns" in err
    # resumed completed sweep reports 100% chunk-skip and, rerun into a
    # fresh dir, >0 prediction-cache hits on the summary line
    rc = pathfind.main(["sweep", "--out", out, "--resume",
                        "--backend", "pipeline"])
    assert rc == 0
    err = capsys.readouterr().err
    assert "evaluated 0 (0 points)" in err
    rc = pathfind.main(["sweep", "--arch", "qwen1.5-0.5b",
                        "--mesh", "2x2", "--mesh", "4x4",
                        "--tilings", "4", "--chunk-size", "4",
                        "--backend", "pipeline",
                        "--out", str(tmp_path / "sweep2")])
    assert rc == 0
    err = capsys.readouterr().err
    hits = int(err.split("cache: prediction ")[1].split(" hits")[0])
    assert hits > 0
    # frontier-only CLI: a full-sweep dir is not a frontier checkpoint —
    # resuming it under --frontier-only must refuse, not re-merge
    rc = pathfind.main(["sweep", "--out", out, "--resume",
                        "--frontier-only"])
    assert rc == 2
    rc = pathfind.main(["sweep", "--arch", "qwen1.5-0.5b",
                        "--mesh", "2x2", "--mesh", "4x4",
                        "--tilings", "4", "--chunk-size", "4",
                        "--frontier-only",
                        "--out", str(tmp_path / "front")])
    assert rc == 0
    cap = capsys.readouterr()
    assert "frontier-only" in cap.err
    assert os.path.exists(os.path.join(str(tmp_path / "front"),
                                       "frontier.jsonl"))


def test_compilation_cache_helper(tmp_path, monkeypatch):
    """One place for the persistent compile cache: the environment's
    directory when set, else the checkout's git-ignored .jax_cache — never
    a path made from the cwd, the pid, the time or an output dir."""
    import jax

    from repro import devices
    prev = jax.config.jax_compilation_cache_dir
    try:
        env_dir = str(tmp_path / "env_cache")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert devices.enable_compilation_cache() == env_dir
        assert jax.config.jax_compilation_cache_dir == env_dir

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = os.path.join(REPO, ".jax_cache")
        monkeypatch.chdir(tmp_path)
        assert devices.enable_compilation_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
        # another process, started from another directory, agrees
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        env["PYTHONPATH"] = os.path.join(REPO, "src")
        child = subprocess.run(
            [sys.executable, "-c", "from repro import devices; "
             "print(devices.compilation_cache_dir())"],
            cwd=str(tmp_path), env=env, capture_output=True, text=True,
            check=True)
        assert child.stdout.strip() == fixed
        with open(os.path.join(REPO, ".gitignore")) as fh:
            assert ".jax_cache/" in fh.read().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


# ------------------------------------------------------------------- CLI
@pytest.mark.slow
def test_cli_sigkill_pipeline_then_resume_matches_serial(tmp_path):
    """Pipeline parity through a hard kill: SIGKILL a pipeline-backend
    sweep mid-flight, resume it, and compare records against a clean
    serial run of the same spec."""
    import signal
    import time

    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(REPO, "src"),
                    env.get("PYTHONPATH", "")) if p)
    out = str(tmp_path / "sweep")
    cmd = [sys.executable, "-m", "repro.pathfind", "sweep",
           "--arch", "qwen1.5-0.5b", "--mesh", "2x2", "--mesh", "2x4",
           "--mesh", "4x4", "--mesh", "2x8", "--mesh", "8x8",
           "--mesh", "4x8",
           "--tilings", "4", "--chunk-size", "1", "--superbatch", "1",
           "--backend", "pipeline", "--out", out]
    proc = subprocess.Popen(cmd, env=env, cwd=REPO,
                            stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL)
    ckpt = os.path.join(out, "checkpoint.jsonl")
    deadline = time.time() + 300
    try:
        while time.time() < deadline:
            if os.path.exists(ckpt) and \
                    len(open(ckpt).read().strip().splitlines()) >= 1:
                break
            if proc.poll() is not None:
                break
            time.sleep(0.2)
        if proc.poll() is None:
            proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
    done_before = 0
    for line in open(ckpt).read().strip().splitlines():
        try:
            json.loads(line)
            done_before += 1
        except json.JSONDecodeError:
            pass
    assert done_before >= 1, "sweep produced no checkpoint before kill"
    resumed = subprocess.run(
        [sys.executable, "-m", "repro.pathfind", "sweep",
         "--out", out, "--resume", "--backend", "pipeline"],
        env=env, capture_output=True, text=True, cwd=REPO, timeout=420)
    assert resumed.returncode == 0, resumed.stderr
    assert f"skipped {done_before} checkpointed" in resumed.stderr
    spec = SweepSpec(
        arches=("qwen1.5-0.5b",),
        mesh_shapes=((2, 2), (2, 4), (4, 4), (2, 8), (8, 8), (4, 8)),
        n_tilings=4, chunk_size=1)
    serial = SweepRunner(spec, backend="serial", cache=None).run()
    rows = [json.loads(ln) for ln in open(os.path.join(out,
                                                       "results.jsonl"))]
    got = {r["key"]: r for r in rows}
    want = _by_key(serial.records)
    assert got.keys() == want.keys()
    for k in want:
        np.testing.assert_allclose(got[k]["time_s"], want[k]["time_s"],
                                   rtol=1e-5)
