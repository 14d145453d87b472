"""The reduction from a profiler trace to busy time, idle share, device
time per executable and labelled idle gaps."""

import pytest

from bench import trace


def _ms(a, b):
    return (a * 1e6, b * 1e6)


def _trace():
    # window 0-100 ms; ops busy 10-30 (two overlapping), 50-60, and one
    # op that straddles the window's end
    ops = [("fusion.1", *_ms(10, 25)), ("fusion.2", *_ms(20, 30)),
           ("copy.3", *_ms(50, 60)), ("fusion.1", *_ms(95, 120)),
           ("early", *_ms(-20, -10))]
    modules = [("jit_scalar(12)", *_ms(10, 30)), ("jit_step(7)", *_ms(50, 60)),
               ("jit_scalar(13)", *_ms(95, 120))]
    spans = [("bench.window", *_ms(0, 100)), ("bench.sweep", *_ms(0, 35)),
             ("bench.sweep", *_ms(45, 100))]
    return trace.Trace(ops={"/device:TPU:0": ops},
                       modules={"/device:TPU:0": modules}, spans=spans)


def test_busy_is_the_union_of_ops_inside_the_window():
    s = trace.reduce(_trace())
    assert s.window_s == pytest.approx(0.1)
    assert s.busy_s == pytest.approx(0.035)          # 20 + 10 + 5 ms
    assert s.idle_share == pytest.approx(0.65)
    assert s.devices == 1


def test_device_time_per_executable_and_op():
    s = trace.reduce(_trace())
    assert s.module_s == pytest.approx({"jit_scalar": 0.025,
                                        "jit_step": 0.010})
    assert s.op_s["fusion.1"] == pytest.approx(0.020)
    assert "early" not in s.op_s
    assert s.top(s.op_s, 1)[0][0] == "fusion.1"


def test_idle_gaps_are_labelled_by_the_host_span_around_them():
    s = trace.reduce(_trace())
    # gaps 0-10 (in the first sweep), 30-50 (its middle falls between
    # the sweeps) and 60-95 (in the second)
    assert s.idle_s["bench.sweep"] == pytest.approx(0.045)
    assert s.idle_s[trace.OUTSIDE] == pytest.approx(0.020)
    assert sum(s.idle_s.values()) == pytest.approx(s.window_s - s.busy_s)


def test_nothing_to_read_gives_nothing():
    t = _trace()
    assert trace.reduce(trace.Trace(ops=t.ops, modules=t.modules,
                                    spans=t.spans[1:])) is None
    assert trace.reduce(trace.Trace(ops={}, modules={},
                                    spans=t.spans)) is None


def test_devices_are_averaged():
    t = _trace()
    t.ops["/device:TPU:1"] = [("fusion.9", *_ms(0, 100))]
    s = trace.reduce(t)
    assert s.devices == 2
    assert s.busy_s == pytest.approx((0.035 + 0.1) / 2)


def test_union_and_gaps():
    busy = trace.union([(5, 8), (1, 3), (2, 4), (9, 30)], 0, 20)
    assert busy == [(1, 4), (5, 8), (9, 20)]
    assert trace.gaps(busy, 0, 20) == [(0, 1), (4, 5), (8, 9)]


def test_a_recorded_chip_trace():
    """40 ms of a traced sweep window on a TPU v5e: the producer's eager
    hardware-generation ops, about a microsecond each, and nothing else."""
    import gzip
    import os

    from jax.profiler import ProfileData
    path = os.path.join(os.path.dirname(__file__), "data",
                        "sweep_window_tpu_v5e.txtpb.gz")
    with gzip.open(path, "rt") as fh:
        xspace = ProfileData.text_proto_to_serialized_xspace(fh.read())
    tr = trace.parse(xspace)
    s = trace.reduce(tr)
    assert s.devices == 1 and s.window_s == pytest.approx(0.04)
    events = tr.modules["/device:TPU:0"]
    assert len(events) == 40
    assert s.busy_s == pytest.approx(sum(e - a for _, a, e in events) / 1e9)
    assert s.idle_share > 0.99
    assert set(s.idle_s) == {"bench.sweep"}
    assert sum(s.module_s.values()) == pytest.approx(s.busy_s)
    assert "jit_multiply" in s.module_s and not s.op_s
