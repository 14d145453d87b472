"""A configuration's own reference module, and the defaults it replaces.

- with no ``"reference"`` key the reference reproduces, bit for bit, the
  records it gave before configurations could bring a module
  (``data/reference_records.json``: 24 points of the Mistral ``sweep``
  grid, one in six of its enumeration at two fixed budget scales, and
  every point of the Qwen3 ``query`` grid at two, with each enumeration);
- a configuration that names a module is judged with that module's
  ``ArchConfig``, ``build_graph``, memory model and strategy axis, and
  with the shared hardware, simulation and traffic model;
- no module of the frozen model imports the program.
"""

import ast
import dataclasses
import glob
import json
import os
import sys
import types
from typing import Tuple

import numpy as np
import pytest

from bench import check, manifest, reference
from bench.crossflow import (age, archconfig, lmgraph, records, simulate,
                             traffic)

HERE = os.path.dirname(os.path.abspath(__file__))
CROSSFLOW = os.path.join(manifest.BENCH, "crossflow")


def _snapshot():
    with open(os.path.join(HERE, "data", "reference_records.json")) as fh:
        return json.load(fh)


SNAPSHOT = _snapshot()


def _text(recs):
    """Records as exact text: floats by their shortest round-trip repr."""
    return [json.dumps(r, sort_keys=True) for r in recs]


@pytest.mark.parametrize("name", sorted(SNAPSHOT))
def test_default_reference_reproduces_its_snapshot(name):
    want = SNAPSHOT[name]
    config = manifest.config(name)
    assert "reference" not in config
    ref = reference.Reference(config, config["grids"][want["grid"]])
    assert ref.keys(want["scales"]) == want["keys"]
    got = [ref.record(check.label_of_key(r["key"])) for r in want["records"]]
    assert _text(got) == _text(want["records"])


# ---------------------------------------------------------------------------
# A toy module: one more ArchConfig field, one more layer kind, a larger
# KV cache
# ---------------------------------------------------------------------------

TOY = "toy_probe_arch"
PROBE = "probe"


@dataclasses.dataclass(frozen=True)
class ProbeArch(archconfig.ArchConfig):
    n_probe_layers: int = 0
    probe_d_ff: int = 0
    probe_tags: Tuple[str, ...] = ()


def _probe_graph(cfg, cell):
    g = lmgraph.build_graph(cfg, cell)
    tokens = cell.global_batch * (1 if cell.kind == "decode"
                                  else cell.seq_len)
    before = set(g.nodes)
    lmgraph._ffn(g, PROBE, cfg, tokens, ["embed"], cell.kind == "train",
                 d_ff=cfg.probe_d_ff)
    for n in set(g.nodes) - before:
        g.nodes[n].meta["repeat"] = cfg.n_probe_layers
    g.validate()
    return g


def _probe_strategies(cfg, cell, mesh_shape):
    """The default axis and one sequence-parallel design more."""
    st = records.candidate_strategies(cfg, cell, mesh_shape)
    return st + [dataclasses.replace(st[0], sp=mesh_shape[-1])]


def _probe_kv(cfg, kv_len, batch):
    return records.kv_cache_bytes(cfg, kv_len, batch) \
        + cfg.n_probe_layers * 512.0 * kv_len * batch


ARCH = {"name": "qwen1.5-0.5b", "family": "dense", "n_layers": 24,
        "d_model": 1024, "n_heads": 16, "n_kv_heads": 16, "d_ff": 2816,
        "vocab_size": 151936, "qkv_bias": True, "ffn_kind": "swiglu",
        "norm_kind": "rmsnorm", "tie_embeddings": True}
GRID = {"meshes": [[2, 4]], "logic": ["N5"], "hbm": ["HBM3"],
        "net": ["IB-NDR-X8"]}
SERVE = {"name": "t.serve", "program_arch": "qwen1.5-0.5b", "arch": ARCH,
         "scenario": "serving-traffic",
         "cells": ["prefill_32k", "decode_32k"], "n_tilings": 4,
         "grids": {"g": GRID}}
PROBED = {**SERVE, "name": "t.probe", "reference": TOY,
          "arch": {**ARCH, "n_probe_layers": 6, "probe_d_ff": 4096,
                   "probe_tags": ["a", "b"]}}


@pytest.fixture
def toy(monkeypatch):
    """The toy module, found by name as `bench/crossflow/<name>.py` would
    be, and spies on the shared copies of AGE, simulate and traffic."""
    mod = types.ModuleType(f"bench.crossflow.{TOY}")
    mod.ArchConfig = ProbeArch
    mod.build_graph = _probe_graph
    mod.kv_cache_bytes = _probe_kv
    mod.candidate_strategies = _probe_strategies
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    calls = {"age": 0, "graphs": [], "traffic": 0}

    def spy(fn, note):
        def wrapped(*a, **k):
            note(a)
            return fn(*a, **k)
        return wrapped

    monkeypatch.setattr(age, "generate", spy(
        age.generate, lambda a: calls.__setitem__("age", calls["age"] + 1)))
    monkeypatch.setattr(simulate, "predict", spy(
        simulate.predict, lambda a: calls["graphs"].append(a[1])))
    monkeypatch.setattr(traffic, "continuous_batching_stats", spy(
        traffic.continuous_batching_stats,
        lambda a: calls.__setitem__("traffic", calls["traffic"] + 1)))
    return calls


def test_a_named_module_builds_the_arch_with_its_fields(toy):
    ref = reference.Reference(PROBED, GRID)
    assert type(ref.cfg) is ProbeArch
    assert (ref.cfg.n_probe_layers, ref.cfg.probe_d_ff) == (6, 4096)
    assert ref.cfg.probe_tags == ("a", "b")
    # the module's strategy axis; what it leaves out stays the default
    plain = reference.Reference(SERVE, GRID).keys([1.0])
    keys = ref.keys([1.0])
    assert keys[:1] == plain and len(keys) == 2 and keys[1] != keys[0]
    assert ref.memory.weight_bytes is records.weight_bytes
    assert ref.memory.kv_shard_degree is records._kv_shard_degree


def test_a_named_module_is_costed_from_its_graph_and_memory_model(toy):
    ref = reference.Reference(PROBED, GRID)
    plain = reference.Reference(SERVE, GRID)
    key = ref.keys([1.0])[0]
    assert plain.keys([1.0]) == [key]           # the default strategy
    lb = check.label_of_key(key)
    hw = ref.hardware(lb)

    rows = np.asarray(ref.phase_rows(lb, hw))
    graphs = toy["graphs"][-2:]
    assert [g.name for g in graphs] == [f"qwen1.5-0.5b|{c}"
                                        for c in SERVE["cells"]]
    assert all(f"{PROBE}.up.fwd" in g.nodes for g in graphs)
    base = np.asarray(plain.phase_rows(lb, hw))
    assert np.all(rows[:, 0] > base[:, 0])      # six more probe layers

    rec = ref.record(lb)
    want = plain.record(lb)
    st = reference.Strategy.parse(lb.strategy)
    dc = archconfig.SHAPE_CELLS["decode_32k"]
    kv = _probe_kv(ref.cfg, dc.seq_len, dc.global_batch) \
        / records._kv_shard_degree(ref.cfg, st)
    assert rec["kv_bytes_per_device"] == kv > want["kv_bytes_per_device"]
    assert rec["weight_bytes_per_device"] == \
        want["weight_bytes_per_device"]
    assert rec["hbm_occupancy"] == (
        (rec["weight_bytes_per_device"] + kv) / float(hw.dram_capacity))
    assert rec["hbm_occupancy"] > want["hbm_occupancy"]
    assert rec["prefill_s"] == float(rows[0][0])


def test_a_named_module_shares_age_simulate_and_traffic(toy):
    ref = reference.Reference(PROBED, GRID)
    lb = check.label_of_key(ref.keys([0.9])[0])
    ref.record(lb)
    assert toy["age"] == 1
    assert len(toy["graphs"]) == 2
    assert toy["traffic"] == 1


def test_a_missing_module_fails_at_construction_naming_key_and_module():
    config = {**SERVE, "reference": "no_such_reference_module"}
    with pytest.raises(ValueError, match=r'"reference".*'
                       r"no_such_reference_module"):
        reference.Reference(config, GRID)


# ---------------------------------------------------------------------------
# The yardstick never follows the program
# ---------------------------------------------------------------------------


def _named_modules():
    """Modules the configuration files name under ``"reference"``."""
    out = set()
    for path in glob.glob(os.path.join(manifest.BENCH, "configs", "*.json")):
        with open(path) as fh:
            name = json.load(fh).get("reference")
        if name is not None:
            out.add(os.path.join(CROSSFLOW, f"{name}.py"))
    return out


MODULES = sorted(set(glob.glob(os.path.join(CROSSFLOW, "*.py")))
                 | _named_modules())


def _imports(tree):
    """Every module an ``import``, ``from ... import`` or
    ``importlib.import_module``/``__import__`` of a literal names."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                # relative: inside bench.crossflow, or out of it
                base = "bench.crossflow" if node.level == 1 else "bench"
                mod = ".".join(filter(None, [base, node.module]))
            else:
                mod = node.module
            yield from (f"{mod}.{a.name}" for a in node.names)
        elif (isinstance(node, ast.Call) and node.args
              and isinstance(node.args[0], ast.Constant)
              and isinstance(node.args[0].value, str)
              and getattr(node.func, "attr",
                          getattr(node.func, "id", None))
              in ("import_module", "__import__")):
            yield node.args[0].value


@pytest.mark.parametrize("path", MODULES,
                         ids=[os.path.basename(p) for p in MODULES])
def test_frozen_model_imports_nothing_of_the_program(path):
    assert os.path.exists(path), f"a configuration names {path}"
    with open(path) as fh:
        tree = ast.parse(fh.read(), path)
    for name in _imports(tree):
        top = name.split(".")[0]
        assert top != "repro", (path, name)
        # the rest of bench imports the program: only bench.crossflow
        if top == "bench":
            assert name.startswith("bench.crossflow"), (path, name)
