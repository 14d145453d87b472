"""How ``correct`` is decided, at a size a CPU test run holds.

- sound runs of each mode (full-record sweeps, frontier sweeps, sizing
  queries) come out correct;
- the control, the reference computed in bfloat16 in the program's place,
  reads far above the record limit, while the same machinery in float32
  reads exactly the eager reference;
- with the timed path broken underneath (an answer altered where it is
  produced, half of each batch left out with the mean of the rest in its
  place, the frontier fold's carried state returned unchanged) a run's
  ``correct`` comes out false.

The harness's look for a chip is skipped; everything else of a run is
driven as on the chip.
"""

import argparse
import time

import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, harness, manifest, reference

ARCH = {"name": "qwen1.5-0.5b", "family": "dense", "n_layers": 24,
        "d_model": 1024, "n_heads": 16, "n_kv_heads": 16, "d_ff": 2816,
        "vocab_size": 151936, "qkv_bias": True, "ffn_kind": "swiglu",
        "norm_kind": "rmsnorm", "tie_embeddings": True}
TRAIN = {"name": "t.train", "program_arch": "qwen1.5-0.5b", "arch": ARCH,
         "scenario": "train", "cells": ["train_4k"], "n_tilings": 4,
         "objectives": ["time_s", "devices"],
         "grids": {"g": {"meshes": [[2, 2], [2, 4]], "logic": ["N7", "N5"],
                         "hbm": ["HBM3"], "net": ["IB-NDR-X8"]}}}
SERVE = {"name": "t.serve", "program_arch": "qwen1.5-0.5b", "arch": ARCH,
         "scenario": "serving-traffic", "cells": ["prefill_32k", "decode_32k"],
         "n_tilings": 4,
         "objectives": ["ttft_p99_s", "cost_device_s_per_token"],
         "grids": {"g": {"meshes": [[2, 2], [2, 4]], "logic": ["N7", "N5"],
                         "hbm": ["HBM3"], "net": ["IB-NDR-X8"]}}}
MIX = {"scale_range": [0.8, 1.2], "scale_step": 1e-5, "warmup": 1,
       "grid": "g"}
MIXES = {
    "sweep": (TRAIN, {**MIX, "mode": "sweep", "scales_per_sweep": 3}),
    "frontier": (TRAIN, {**MIX, "mode": "frontier", "scales_per_sweep": 3}),
    "query": (SERVE, {**MIX, "mode": "query", "qps_range": [1, 50],
                      "slo": {"ttft_p99": 60.0, "tpot_p50": 0.5}}),
}


@pytest.fixture
def run_cell(monkeypatch, tmp_path):
    """A function running one small cell through `harness.measure`."""
    from repro import devices
    monkeypatch.setattr(devices, "enable_compilation_cache", lambda: None)
    monkeypatch.setattr(harness, "WORKDIR", str(tmp_path))
    monkeypatch.setattr(check, "N_RECORDS", 64)
    monkeypatch.setattr(check, "N_COVER", 64)
    # nothing a fault could hide behind: no cached prediction, no
    # executable built before the fault was planted
    from repro.core import pathfinder
    pathfinder.clear_prediction_cache()
    pathfinder.clear_compiled_caches()

    def run(mode, seed=2**31 + 17):
        config, mix = MIXES[mode]
        monkeypatch.setattr(manifest, "config", lambda name: config)
        monkeypatch.setattr(manifest, "traffic", lambda name: mix)
        cell = {"name": "t." + mode, "config": config["name"],
                "traffic": mode, "chips": 1}
        bench = {"end_to_end": [], "per_layer": [], "workloads": [cell]}
        monkeypatch.setattr(manifest, "load", lambda root=None: bench)
        args = argparse.Namespace(workload=cell["name"], seed=seed,
                                  seconds=0.0, trace=0)
        result, numbers = harness.measure(args, cell, time.time())
        return result, numbers.items
    return run


@pytest.mark.parametrize("mode", sorted(MIXES))
def test_sound_runs_are_correct(run_cell, mode):
    result, items = run_cell(mode)
    assert result["correct"], items
    assert result["attempted"] == 1 and result["failed"] == 0
    assert items["record_rel_diff"][0] < 1e-5


def test_control_in_bfloat16_fails_the_record_limit():
    ref = reference.Reference(TRAIN, TRAIN["grids"]["g"])
    keys = ref.keys([0.9, 1.1])
    gaps32, gaps16 = [], []
    for key in keys[::3]:
        lb = check.label_of_key(key)
        want = ref.record(lb)
        gaps32.append(reference.compare_records(
            ref.control_record(lb, jnp.float32), want)[0])
        gaps16.append(reference.compare_records(
            ref.control_record(lb, jnp.bfloat16), want)[0])
    assert max(gaps32) == 0.0
    assert min(gaps16) > 10 * reference.REF_RTOL


def _alter_one_answer(rows):
    """One design point's answer, halved where it is produced."""
    return jnp.asarray(rows).at[0].multiply(0.5)


def _broken_frontier(fault=None, stuck=False):
    """`PipelineExecutor._frontier_build` with its rows broken by
    ``fault``, or with the carried state returned unchanged."""
    import jax
    from repro.core import pathfinder

    def build_for(self, group, capacity):
        def build():
            design = self._design_scalar(group)
            fold = group.skel.fold

            def step(hw, idx, state):
                if stuck:
                    return state
                rows = fault(jax.vmap(design)(hw))
                vals = jax.vmap(fold)(rows, hw)
                vals = jnp.where((idx < 0)[:, None], jnp.inf, vals)
                payload = rows.reshape(rows.shape[0], -1)
                return pathfinder.frontier_merge(state, vals, payload, idx)
            return jax.jit(step, donate_argnums=2)
        return build
    return build_for


@pytest.mark.parametrize("mode", sorted(MIXES))
def test_an_answer_altered_is_not_correct(run_cell, monkeypatch, mode):
    from repro.core import sweeppipeline
    rows = sweeppipeline._BucketOut.rows
    monkeypatch.setattr(
        sweeppipeline._BucketOut, "rows",
        lambda self: np.asarray(_alter_one_answer(rows(self)),
                                dtype=np.float64))
    monkeypatch.setattr(sweeppipeline.PipelineExecutor, "_frontier_build",
                        _broken_frontier(_alter_one_answer))
    result, items = run_cell(mode, seed=2**31 + 101)
    assert not result["correct"], items


@pytest.mark.parametrize("mode", sorted(MIXES))
def test_half_the_batch_left_out_is_not_correct(run_cell, monkeypatch,
                                                mode):
    """Each batch's second half never reaches the evaluator: the mean of
    the first half's hardware rows is evaluated in its place."""
    from repro.core import sweeppipeline
    gather = sweeppipeline.PipelineExecutor._gather

    def half(self, g):
        rows = gather(self, g).copy()
        n = rows.shape[0] // 2
        if n:
            rows[n:] = rows[:n].mean(axis=0)
        return rows
    monkeypatch.setattr(sweeppipeline.PipelineExecutor, "_gather", half)
    result, items = run_cell(mode, seed=2**31 + 303)
    assert not result["correct"], items


def test_a_frontier_state_left_unchanged_is_not_correct(run_cell,
                                                        monkeypatch):
    from repro.core import sweeppipeline
    monkeypatch.setattr(sweeppipeline.PipelineExecutor, "_frontier_build",
                        _broken_frontier(stuck=True))
    result, items = run_cell("frontier", seed=2**31 + 202)
    assert not result["correct"], items
