"""Distributed sweep fabric tests (ISSUE-7 tentpole).

Covers: the lease protocol (exclusive claim, expiry + reclaim-by-rename,
heartbeat renewal, torn lease files), directory init guards, the
deterministic first-wins shard merge, in-process worker parity against the
serial backend (full and frontier mode), and the fault-injection
kill-matrix: real `pathfind sweep-worker` processes SIGKILL'd mid-chunk /
mid-commit / mid-renewal, a deliberately stalled worker whose expired
leases are reclaimed, and SIGTERM preemption that commits in-flight work
and exits clean.  The fleet-wide invariant throughout: a committed chunk
is NEVER re-evaluated, and the merged output is duplicate-free and
matches the serial backend.
"""

import glob
import json
import os
import signal
import time

import pytest

import fabrichelpers as fh
from repro.core import sweepexec, sweepfabric, sweeprunner
from repro.core.sweepfabric import (FabricCoordinator, FabricWorker,
                                    LeaseManager)
from repro.core.sweeprunner import SweepRunner, SweepSpec

SPEC = SweepSpec(arches=("qwen1.5-0.5b",), mesh_shapes=((2, 2), (4, 4)),
                 scenario="train", logic_nodes=("N7", "N5"),
                 n_tilings=4, chunk_size=1)            # 4 points, 4 chunks

# spans capacity-infeasible AND SLO-wall-failing points (percentile walls
# from the traffic scenario) — the fabric must agree with the serial
# backend on every regime, not just the happy path
TRAFFIC_SPEC = SweepSpec(
    arches=("qwen1.5-0.5b",), mesh_shapes=((2, 2), (4, 4)),
    scenario="serving-traffic", logic_nodes=("N7",),
    budget_scales=(0.9, 1.1), n_tilings=4, chunk_size=4,
    scenario_params={"qps": 0.1, "prefill_chunk": [1024.0, 8192.0],
                     "slo_ttft_p99": [5.0, 50.0]})     # 16 points, 4 chunks

CHUNKS = sweeprunner.make_chunks(sweeprunner.enumerate_labels(SPEC),
                                 SPEC.chunk_size)
FP = SPEC.fingerprint()


@pytest.fixture(scope="module")
def serial_records():
    return SweepRunner(SPEC, backend="serial", cache=None).run().records


# ------------------------------------------------------- one process per chip
def test_coordinator_refuses_workers_on_accelerator_host(tmp_path,
                                                         monkeypatch):
    """Worker processes would contend for the chip: refused with the
    reason before any is spawned, and the CLI says so with rc 2."""
    from repro import devices, pathfind
    monkeypatch.setattr(devices, "children_platform", lambda env=None: "tpu")
    monkeypatch.setattr(sweepfabric.subprocess, "Popen",
                        lambda *a, **k: pytest.fail("worker spawned"))
    out = str(tmp_path / "fab")
    with pytest.raises(devices.AcceleratorBusyError, match="'tpu'"):
        FabricCoordinator(SPEC, out, workers=2).run()
    assert pathfind.main(["sweep", "--arch", "qwen1.5-0.5b", "--mesh", "2x2",
                          "--workers", "2", "--out",
                          str(tmp_path / "cli")]) == 2


def test_children_platform_reads_cpu_pin_without_a_probe(monkeypatch):
    from repro import devices
    monkeypatch.setattr(devices.subprocess, "run",
                        lambda *a, **k: pytest.fail("probe process started"))
    assert devices.children_platform({"JAX_PLATFORMS": "cpu"}) == "cpu"


# ------------------------------------------------------------ lease protocol
def test_lease_claim_is_exclusive(tmp_path):
    a = LeaseManager(str(tmp_path), "a")
    b = LeaseManager(str(tmp_path), "b")
    assert a.claim(0)
    assert not b.claim(0)                  # O_EXCL: exactly one winner
    assert a.owns(0) and not b.owns(0)
    assert a.holder(0) == "a"
    assert b.claim(1)                      # other chunks unaffected


def test_lease_steal_requires_expiry(tmp_path):
    a = LeaseManager(str(tmp_path), "a", ttl_s=0.3)
    b = LeaseManager(str(tmp_path), "b", ttl_s=0.3)
    assert a.claim(0)
    assert not b.steal_expired(0)          # still live
    time.sleep(0.4)
    assert b.steal_expired(0)              # expired: rename-steal wins
    assert b.owns(0) and not a.owns(0)
    assert a.renew([0]) == [0]             # old holder learns it lost


def test_lease_renew_pushes_expiry(tmp_path):
    a = LeaseManager(str(tmp_path), "a", ttl_s=0.6)
    b = LeaseManager(str(tmp_path), "b", ttl_s=0.6)
    assert a.claim(0)
    time.sleep(0.4)
    assert a.renew([0]) == []              # heartbeat
    time.sleep(0.3)                        # past the ORIGINAL expiry
    assert not b.steal_expired(0)          # renewal kept it alive
    time.sleep(0.4)                        # past the renewed expiry
    assert b.steal_expired(0)


def test_lease_torn_file_falls_back_to_mtime(tmp_path):
    a = LeaseManager(str(tmp_path), "a", ttl_s=5.0)
    path = os.path.join(str(tmp_path), "leases", "chunk_0.json")
    with open(path, "w") as fhdl:
        fhdl.write('{"worker": "dead", "exp')      # torn mid-write
    assert not a.steal_expired(0)          # fresh mtime: not stealable yet
    os.utime(path, (time.time() - 60, time.time() - 60))
    assert a.steal_expired(0)              # old + unreadable = expired
    assert a.owns(0)


def test_lease_release_only_own(tmp_path):
    a = LeaseManager(str(tmp_path), "a")
    b = LeaseManager(str(tmp_path), "b")
    assert a.claim(3)
    b.release(3)                           # not b's to drop
    assert a.owns(3)
    a.release(3)
    assert a.holder(3) is None
    assert b.claim(3)                      # released chunk claimable again


# ------------------------------------------------------------ dir init
def test_init_dir_guards_mode_and_spec(tmp_path):
    out = str(tmp_path / "fab")
    head = sweepfabric.init_dir(SPEC, out)
    assert head["mode"] == "full"
    sweepfabric.init_dir(SPEC, out)        # re-join: idempotent
    with pytest.raises(ValueError, match="mode"):
        sweepfabric.init_dir(SPEC, out, frontier_only=True)
    import dataclasses
    other = dataclasses.replace(SPEC, logic_nodes=("N7",))
    with pytest.raises(ValueError, match="spec changed"):
        sweepfabric.init_dir(other, out)
    spec2, fabric = sweepfabric.load_dir(out)
    assert spec2.fingerprint() == FP and fabric["mode"] == "full"


# ------------------------------------------------------------ shard merge
def test_merge_results_first_wins_on_double_commit(tmp_path):
    """Even if an expired-lease race ever let two workers commit the same
    chunk, exactly one copy survives the merge, deterministically."""
    out = str(tmp_path / "fab")
    sweepfabric.init_dir(SPEC, out)
    for wid, committed in (("a", (0, 1)), ("b", (0, 2))):
        sp = sweepfabric.shard_paths(out, wid)
        j = sweepexec.ChunkJournal(sp["results"], sp["checkpoint"]).open()
        for i in committed:
            j.commit(i, CHUNKS[i].hash(FP),
                     [{"key": f"pt{i}", "src": wid}])
        j.close()
    records, done = sweepfabric.merge_results(out)
    assert sorted(done) == [0, 1, 2]
    by_key = {r["key"]: r for r in records}
    assert by_key["pt0"]["src"] == "a"     # sorted shard order: a wins
    assert by_key["pt1"]["src"] == "a" and by_key["pt2"]["src"] == "b"
    assert all("chunk" not in r for r in records)
    with open(os.path.join(out, "checkpoint.jsonl")) as fhdl:
        lines = [json.loads(ln) for ln in fhdl if ln.strip()]
    assert [ln["chunk"] for ln in lines] == [0, 1, 2]
    assert all(ln["hash"] == CHUNKS[ln["chunk"]].hash(FP) for ln in lines)


def test_worker_cmd_carries_fabric_knobs(tmp_path):
    coord = FabricCoordinator(SPEC, str(tmp_path), workers=0,
                              superbatch=8, claim_batch=2,
                              eval_delay_s=0.01)
    cmd = coord.worker_cmd()
    assert "sweep-worker" in cmd
    for flag, val in (("--dir", str(tmp_path)), ("--superbatch", "8"),
                      ("--claim-batch", "2"), ("--eval-delay", "0.01")):
        assert cmd[cmd.index(flag) + 1] == val


# ------------------------------------------------------------ in-process
def test_worker_full_mode_matches_serial(tmp_path, serial_records):
    out = str(tmp_path / "fab")
    sweepfabric.init_dir(SPEC, out)
    stats = FabricWorker(out, ttl_s=60.0, claim_batch=2).run()
    assert stats.n_chunks_committed == len(CHUNKS)
    assert stats.n_points == len(serial_records)
    assert not stats.preempted and stats.n_lost_leases == 0
    records, done = sweepfabric.merge_results(out)
    assert len(done) == len(CHUNKS)
    fh.assert_no_duplicate_point_keys(records)
    fh.assert_records_match(records, serial_records)
    # merged layout is the standard single-host one
    assert [r["key"] for r in fh.merged_record_lines(out)] == \
        [r["key"] for r in records]


def test_two_sequential_workers_split_the_sweep(tmp_path, serial_records):
    """Worker A commits half and leaves; worker B (fresh incarnation,
    fresh shard) finishes the rest off A's committed state."""
    out = str(tmp_path / "fab")
    sweepfabric.init_dir(SPEC, out)
    a = FabricWorker(out, worker_id="wa", ttl_s=60.0, claim_batch=1,
                     max_chunks=2).run()
    assert a.n_chunks_committed == 2
    b = FabricWorker(out, worker_id="wb", ttl_s=60.0, claim_batch=2).run()
    assert b.n_chunks_committed == len(CHUNKS) - 2
    records, done = sweepfabric.merge_results(out)
    assert len(done) == len(CHUNKS)
    fh.assert_records_match(records, serial_records)
    fh.assert_no_committed_chunk_reevaluated(out)
    ckpts = glob.glob(os.path.join(out, "shards", "checkpoint.*.jsonl"))
    assert len(ckpts) == 2                 # one shard per incarnation


def test_worker_frontier_mode_matches_single_host(tmp_path):
    out = str(tmp_path / "fab")
    sweepfabric.init_dir(SPEC, out, frontier_only=True)
    a = FabricWorker(out, worker_id="wa", ttl_s=60.0, claim_batch=1,
                     max_chunks=2).run()
    assert a.n_chunks_committed == 2
    b = FabricWorker(out, worker_id="wb", ttl_s=60.0, claim_batch=2).run()
    assert a.n_chunks_committed + b.n_chunks_committed == len(CHUNKS)
    records, n_over, done = sweepfabric.merge_frontier(out)
    assert len(done) == len(CHUNKS) and n_over == 0
    single = SweepRunner(SPEC, backend="pipeline",
                         cache=None).run(frontier_only=True)
    assert single.n_frontier_overflowed == 0
    fh.assert_records_match(records, single.records)
    assert os.path.exists(os.path.join(out, "frontier.jsonl"))
    assert os.path.exists(os.path.join(out, "frontier_state.npz"))
    fh.assert_no_committed_chunk_reevaluated(out)


# ------------------------------------------------------------ kill matrix
@pytest.mark.slow
@pytest.mark.parametrize("point,nth", [
    ("eval", 2),        # mid-chunk: evaluated, nothing written
    ("post_rows", 2),   # torn commit: rows on disk, no done-line
    ("renew", 1),       # mid-heartbeat: renewal tmp written, not renamed
])
def test_kill_matrix_survivor_resumes(tmp_path, point, nth,
                                      serial_records):
    out = str(tmp_path / "fab")
    xla = str(tmp_path / "xla")
    sweepfabric.init_dir(SPEC, out)
    token = str(tmp_path / "kill.token")
    victim = fh.spawn_worker(
        out, ttl=3.0, claim_batch=4, xla_cache=xla,
        env={"REPRO_FABRIC_KILL": f"{point}:{nth}:{token}"})
    fh.wait_procs([victim], 240.0)
    assert victim.returncode == -signal.SIGKILL
    assert os.path.exists(token), "injection point never fired"
    survivor = fh.spawn_worker(out, ttl=60.0, claim_batch=4,
                               xla_cache=xla)
    fh.wait_procs([survivor], 240.0)
    assert survivor.returncode == 0
    records, done = sweepfabric.merge_results(out)
    assert len(done) == len(CHUNKS), "sweep did not resume to completion"
    fh.assert_no_duplicate_point_keys(records)
    fh.assert_no_committed_chunk_reevaluated(out)
    fh.assert_records_match(records, serial_records)


@pytest.mark.slow
def test_stalled_worker_leases_are_reclaimed(tmp_path, serial_records):
    """A worker claims every chunk then stalls past its TTL without
    heartbeating; a healthy worker reclaims the expired leases and does
    all the work.  The stalled worker wakes, discovers it lost its whole
    batch, and exits clean with zero commits."""
    out = str(tmp_path / "fab")
    xla = str(tmp_path / "xla")
    sweepfabric.init_dir(SPEC, out)
    stalled = fh.spawn_worker(out, ttl=2.0, claim_batch=4, xla_cache=xla,
                              env={"REPRO_FABRIC_STALL_S": "20"})
    fh.wait_for(
        lambda: len(glob.glob(os.path.join(out, "leases",
                                           "chunk_*.json"))) == 4,
        60.0, "the stalled worker to claim every lease")
    healthy = fh.spawn_worker(out, ttl=60.0, claim_batch=4,
                              xla_cache=xla)
    fh.wait_procs([stalled, healthy], 240.0)
    assert stalled.returncode == 0 and healthy.returncode == 0
    by_pid = {s["pid"]: s for s in fh.read_stats(out)}
    st, he = by_pid[stalled.pid], by_pid[healthy.pid]
    assert st["n_chunks_committed"] == 0 and st["n_lost_leases"] >= 1
    assert he["n_chunks_committed"] == len(CHUNKS)
    for i in range(len(CHUNKS)):           # healthy worker holds them now
        assert LeaseManager(out, "probe").holder(i) == he["worker"]
    records, done = sweepfabric.merge_results(out)
    assert len(done) == len(CHUNKS)
    fh.assert_no_duplicate_point_keys(records)
    fh.assert_records_match(records, serial_records)


@pytest.mark.slow
def test_sigterm_commits_inflight_then_exits_clean(tmp_path,
                                                   serial_records):
    out = str(tmp_path / "fab")
    xla = str(tmp_path / "xla")
    sweepfabric.init_dir(SPEC, out)
    w = fh.spawn_worker(out, ttl=60.0, claim_batch=1, xla_cache=xla,
                        extra_args=["--eval-delay", "1.5"])
    fh.wait_for(lambda: any(s.get("committed") for s in
                            fh.read_stats(out)),
                240.0, "the first chunk commit")
    w.send_signal(signal.SIGTERM)
    fh.wait_procs([w], 120.0)
    assert w.returncode == 0               # preemption is a CLEAN exit
    s = next(s for s in fh.read_stats(out) if s["pid"] == w.pid)
    assert s["preempted"] is True
    assert 1 <= s["n_chunks_committed"] < len(CHUNKS)
    # unfinished leases were released on the way out: the successor never
    # has to wait out a TTL
    committed_chunks = {c for c, _ in s["committed"]}
    probe = LeaseManager(out, "probe")
    for i in range(len(CHUNKS)):
        if probe.holder(i) == s["worker"]:
            assert i in committed_chunks, (
                f"preempted worker still holds the lease of "
                f"UNFINISHED chunk {i}")
    # preemption cost zero finished work: a fresh worker completes the rest
    w2 = fh.spawn_worker(out, ttl=60.0, claim_batch=4, xla_cache=xla)
    fh.wait_procs([w2], 240.0)
    records, done = sweepfabric.merge_results(out)
    assert len(done) == len(CHUNKS)
    fh.assert_no_committed_chunk_reevaluated(out)
    fh.assert_records_match(records, serial_records)


@pytest.mark.slow
def test_frontier_kill_and_cross_worker_merge(tmp_path):
    """Frontier mode under fire: the victim dies before its first state
    checkpoint lands, two concurrent survivors split the reclaimed work,
    and the cross-worker merge equals the single-host frontier."""
    out = str(tmp_path / "fab")
    xla = str(tmp_path / "xla")
    sweepfabric.init_dir(SPEC, out, frontier_only=True)
    token = str(tmp_path / "kill.token")
    victim = fh.spawn_worker(
        out, ttl=3.0, claim_batch=2, xla_cache=xla,
        env={"REPRO_FABRIC_KILL": f"post_rows:1:{token}"})
    fh.wait_procs([victim], 240.0)
    assert victim.returncode == -signal.SIGKILL
    survivors = [fh.spawn_worker(out, ttl=60.0, claim_batch=1,
                                 xla_cache=xla) for _ in range(2)]
    fh.wait_procs(survivors, 300.0)
    assert all(pr.returncode == 0 for pr in survivors)
    records, n_over, done = sweepfabric.merge_frontier(out)
    assert len(done) == len(CHUNKS) and n_over == 0
    fh.assert_no_committed_chunk_reevaluated(out)
    single = SweepRunner(SPEC, backend="pipeline",
                         cache=None).run(frontier_only=True)
    fh.assert_records_match(records, single.records)


# ------------------------------------------------------------ parity (grid)
@pytest.mark.slow
def test_two_worker_fabric_matches_serial_on_traffic_grid(tmp_path):
    """2 concurrent workers on the serving-traffic grid — percentile SLO
    walls, capacity-infeasible points and all — against the serial
    backend."""
    serial = SweepRunner(TRAFFIC_SPEC, backend="serial",
                         cache=None).run()
    regimes = {(r["feasible"], r["slo_ok"]) for r in serial.records}
    assert (False, False) in regimes, "grid lost its infeasible points"
    assert (True, False) in regimes, "grid lost its SLO-wall failures"
    out = str(tmp_path / "fab")
    xla = str(tmp_path / "xla")
    sweepfabric.init_dir(TRAFFIC_SPEC, out)
    workers = [fh.spawn_worker(out, ttl=60.0, claim_batch=1,
                               xla_cache=xla) for _ in range(2)]
    fh.wait_procs(workers, 300.0)
    assert all(pr.returncode == 0 for pr in workers)
    records, done = sweepfabric.merge_results(out)
    n_chunks = len(sweeprunner.make_chunks(
        sweeprunner.enumerate_labels(TRAFFIC_SPEC),
        TRAFFIC_SPEC.chunk_size))
    assert len(done) == n_chunks
    fh.assert_no_duplicate_point_keys(records)
    fh.assert_no_committed_chunk_reevaluated(out)
    fh.assert_records_match(records, serial.records)


@pytest.mark.slow
def test_coordinator_end_to_end(tmp_path, serial_records):
    """The user-facing path: coordinator spawns 2 local workers, waits,
    merges — `FabricStats` mirrors what the CLI prints."""
    out = str(tmp_path / "fab")
    coord = FabricCoordinator(
        SPEC, out, workers=2, ttl_s=60.0, poll_s=0.3, claim_batch=1,
        worker_env={"PYTHONPATH": os.pathsep.join(
            p for p in (os.path.join(fh.REPO, "src"),
                        os.environ.get("PYTHONPATH", "")) if p),
            "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "xla")})
    stats = coord.run()
    assert stats.complete and stats.mode == "full"
    assert stats.n_chunks_committed == len(CHUNKS)
    assert stats.n_points_total == len(serial_records)
    fh.assert_no_duplicate_point_keys(stats.records)
    fh.assert_records_match(stats.records, serial_records)
    assert os.path.exists(os.path.join(out, "results.jsonl"))
