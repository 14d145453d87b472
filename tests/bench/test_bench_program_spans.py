"""The readers of the program's own spans (`bench.program_spans` and the
six per-layer metrics on it), on hand-built traces written as the profiler
writes them."""

import pytest

from bench import harness, manifest, program_spans as ps
from bench import trace as btrace

WINDOW = ("bench.window", 0, 100)


def _xspace(device, lines):
    """A serialized XSpace: ``device`` is [(line, [(name, a_ms, b_ms)])]
    on one TPU, ``lines`` the same for host threads."""
    from jax.profiler import ProfileData

    def plane(pid, name, plane_lines):
        names = sorted({n for _, evs in plane_lines for n, _, _ in evs})
        meta = {n: i + 1 for i, n in enumerate(names)}
        out = [f'planes {{ id: {pid} name: "{name}"']
        for lid, (lname, evs) in enumerate(plane_lines, 1):
            out.append(f'  lines {{ id: {lid} name: "{lname}" '
                       f'timestamp_ns: 0')
            out += [f"    events {{ metadata_id: {meta[n]} "
                    f"offset_ps: {int(a * 1e9)} "
                    f"duration_ps: {int((b - a) * 1e9)} }}"
                    for n, a, b in evs]
            out.append("  }")
        out += [f'  event_metadata {{ key: {i} value {{ id: {i} '
                f'name: "{n}" }} }}' for n, i in meta.items()]
        return "\n".join(out + ["}"])
    text = plane(1, "/device:TPU:0", device) + "\n" + \
        plane(2, "/host:CPU", lines)
    return ProfileData.text_proto_to_serialized_xspace(text)


# threaded sweep, window 0-100 ms: the dispatcher waits for the producer
# except while it dispatches; the device runs 30-35 (inside a wait),
# 40-41.5 and 80-81 (inside the dispatches)
DEVICE = [("XLA Modules", [("jit_multiply(1)", 30, 35),
                           ("jit_scalar(2)", 40, 41.5),
                           ("jit_scalar(2)", 80, 81)]),
          ("XLA Ops", [("fusion.1 = f32[] multiply()", 30, 35)])]
MAIN = ("python3", [WINDOW, ("bench.sweep", 2, 98), (ps.RUN, 5, 95),
                    (ps.WAIT, 5, 40), (ps.DISPATCH, 40, 42),
                    (ps.WAIT, 42, 80), (ps.DISPATCH, 80, 82),
                    (ps.WAIT, 82, 85)])
# two AGE spans overlap (8-20 and 15-30): their union is 22 ms
PRODUCER = ("python3", [(ps.PACK, 6, 38), (ps.AGE, 8, 20), (ps.AGE, 15, 30),
                        (ps.PACK, 45, 78), (ps.AGE, 50, 60)])
WRITER = ("python3", [(ps.FINALIZE, 42, 60), (ps.COMMIT, 50, 55),
                      (ps.COMMIT, 55, 58), (ps.FINALIZE, 82, 90),
                      (ps.COMMIT, 85, 88)])

# three queries on one thread each for dispatch, producer and writer
QUERY_MAIN = ("python3", [("bench.window", 0, 150),
                          (ps.RUN, 10, 40), (ps.WAIT, 10.5, 30.5),
                          (ps.DISPATCH, 30.5, 31),
                          (ps.RUN, 50, 90), (ps.WAIT, 51, 70),
                          (ps.DISPATCH, 70, 71),
                          (ps.RUN, 100, 120), (ps.WAIT, 101, 110),
                          (ps.DISPATCH, 110, 111)])
QUERY_PRODUCER = ("python3", [(ps.PACK, 11, 30), (ps.AGE, 12, 28),
                              (ps.PACK, 52, 69), (ps.AGE, 53, 66),
                              (ps.AGE, 60, 68),
                              (ps.PACK, 102, 109), (ps.AGE, 103, 108)])
QUERY_WRITER = ("python3", [(ps.FINALIZE, 31, 36), (ps.COMMIT, 35, 36),
                            (ps.FINALIZE, 71, 80), (ps.COMMIT, 79, 80),
                            (ps.FINALIZE, 111, 118)])
QUERY_DEVICE = [("XLA Modules", [("jit_scalar(2)", 30.6, 30.9),
                                 ("jit_scalar(2)", 70.2, 70.8)])]


def _run(tmp_path, monkeypatch, xspace, mode="sweep", points=500,
         window_s=None):
    """Write the trace where the harness keeps it and build the run the
    readers are given."""
    monkeypatch.setattr(harness, "WORKDIR", str(tmp_path))
    d = tmp_path / "cell" / "trace" / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(xspace)
    summary = btrace.reduce(btrace.parse(xspace))
    if window_s is not None:
        summary.window_s = window_s
    return harness.Run(mode=mode, points=points, window_s=summary.window_s,
                       latencies_s=[], spans={}, setup={}, summary=summary)


def _read(name, run):
    return manifest.reader(name)(run)


SWEEP_METRICS = ["producer.ms_per_kpoint", "producer.age_ms_per_kpoint",
                 "writer.ms_per_kpoint", "device.idle_behind_producer.sweep"]
QUERY_METRICS = ["query.age_ms_p50", "query.runner_self_ms_p50"]


def test_sweep_readers(tmp_path, monkeypatch):
    run = _run(tmp_path, monkeypatch,
               _xspace(DEVICE, [MAIN, PRODUCER, WRITER]))
    # per 1,000 of the run's 500 points
    assert _read("producer.ms_per_kpoint", run) == pytest.approx(130.0)
    assert _read("producer.age_ms_per_kpoint", run) == pytest.approx(64.0)
    assert _read("writer.ms_per_kpoint", run) == pytest.approx(52.0)
    # the dispatcher waits 76 ms, 5 of them with the device busy; its
    # dispatches, the writer's time after the last wait and the harness's
    # time around the run do not count
    assert _read("device.idle_behind_producer.sweep", run) \
        == pytest.approx(71.0)
    for name in QUERY_METRICS:
        assert _read(name, run) is None


def test_inline_producer_counts_as_waiting(tmp_path, monkeypatch):
    main = ("python3", [WINDOW, (ps.RUN, 0, 100), (ps.PACK, 10, 50),
                        (ps.AGE, 20, 30), (ps.DISPATCH, 50, 52),
                        (ps.FINALIZE, 52, 70), (ps.COMMIT, 60, 70)])
    run = _run(tmp_path, monkeypatch, _xspace(DEVICE, [main]), points=1000)
    # the pack is 10-50 with the device busy 30-35 and 40-41.5
    assert _read("device.idle_behind_producer.sweep", run) \
        == pytest.approx(33.5)
    assert _read("producer.ms_per_kpoint", run) == pytest.approx(40.0)
    assert _read("producer.age_ms_per_kpoint", run) == pytest.approx(10.0)
    assert _read("writer.ms_per_kpoint", run) == pytest.approx(18.0)


def test_query_readers(tmp_path, monkeypatch):
    run = _run(tmp_path, monkeypatch,
               _xspace(QUERY_DEVICE, [QUERY_MAIN, QUERY_PRODUCER,
                                      QUERY_WRITER]), mode="query", points=18)
    # AGE per query 16, 15 (two overlapping spans) and 5 ms
    assert _read("query.age_ms_p50", run) == pytest.approx(15.0)
    # self time: 30 - 25.5, 40 - 29 and 20 - 17 ms; the children lie on
    # the producer's and writer's threads
    assert _read("query.runner_self_ms_p50", run) == pytest.approx(4.5)
    for name in SWEEP_METRICS:
        assert _read(name, run) is None


def test_idle_by_stage_splits_waits_by_the_producer(tmp_path):
    sp = ps.parse(_xspace(DEVICE, [MAIN, PRODUCER, WRITER]))
    got = ps.idle_by_stage(sp)
    assert got == pytest.approx({
        ps.OUTSIDE: 0.010, ps.RUN: 0.010, ps.DISPATCH: 0.0015,
        f"{ps.WAIT} > {ps.AGE}": 0.032,
        f"{ps.WAIT} > {ps.PACK}": 0.028,
        f"{ps.WAIT} > producer outside repro spans": 0.011})
    assert sum(got.values()) == pytest.approx(0.1 - 0.0075)
    assert ps.idle_behind_producer(sp) == pytest.approx(71.0)


@pytest.mark.parametrize("metric", SWEEP_METRICS + QUERY_METRICS)
def test_nothing_to_read_gives_none(tmp_path, monkeypatch, metric):
    mode = "query" if metric in QUERY_METRICS else "sweep"
    # the parent program: the harness's spans and no repro.* span
    parent = _xspace(DEVICE, [("python3", [WINDOW, ("bench.sweep", 2, 98)])])
    assert _read(metric, _run(tmp_path / "a", monkeypatch, parent,
                              mode=mode)) is None
    full = _xspace(QUERY_DEVICE, [QUERY_MAIN, QUERY_PRODUCER, QUERY_WRITER])
    assert _read(metric, _run(tmp_path / "b", monkeypatch, full,
                              mode=mode)) is not None
    # a trace whose window is not the run's
    assert _read(metric, _run(tmp_path / "c", monkeypatch, full, mode=mode,
                              window_s=0.149)) is None
    # no trace at all
    run = _run(tmp_path / "d", monkeypatch, full, mode=mode)
    run.summary = None
    assert _read(metric, run) is None


def test_a_recorded_chip_query(tmp_path, monkeypatch):
    """One query cut from a traced window of the query cell on a TPU v5e:
    AGE's eager operations on the device while the dispatcher waits for
    the producer, then one dispatch, the writer, and the sizing."""
    import gzip
    import os

    from jax.profiler import ProfileData
    path = os.path.join(os.path.dirname(__file__), "data",
                        "query_window_tpu_v5e.txtpb.gz")
    with gzip.open(path, "rt") as fh:
        xspace = ProfileData.text_proto_to_serialized_xspace(fh.read())
    run = _run(tmp_path, monkeypatch, xspace, mode="query", points=6)
    assert run.window_s == pytest.approx(0.151114081)
    assert _read("query.age_ms_p50", run) == pytest.approx(116.991943)
    assert _read("query.runner_self_ms_p50", run) == pytest.approx(1.20475)
    sp = ps.parse(xspace)
    count = {}
    for name, *_ in sp.events:
        count[name] = count.get(name, 0) + 1
    assert count == {ps.RUN: 1, ps.PACK: 1, ps.AGE: 1, ps.WAIT: 2,
                     ps.DISPATCH: 1, ps.FINALIZE: 1, ps.COMMIT: 1}
    idle = ps.idle_by_stage(sp)
    assert idle[f"{ps.WAIT} > {ps.AGE}"] == pytest.approx(0.116084388)
    assert sum(idle.values()) == pytest.approx(
        run.summary.window_s - run.summary.busy_s)
