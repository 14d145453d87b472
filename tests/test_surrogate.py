"""Learned surrogate + acquisition-driven exploration tests (ISSUE-9).

Covers: torn-line-tolerant training-set ingestion (the surrogate reads
sweep rows through `sweepexec.iter_jsonl`, so an interrupted writer's
partial tail never reaches the training set), featurization over the
spec's enumeration, the jit(vmap) ensemble fit + epistemic predict,
exact hypervolume, the acquisition layer's invariants (sign-flip
equivariance via `canonical_signs`, permutation-independence on exact
ties — both property-based), advisory chunk ordering end to end
(`order_chunks`, order.json round-trip, `FabricWorker` claim order),
and the explore loop's budget / resume / stopping semantics.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

from repro.core import (pathfinder, surrogate, sweepexec, sweepfabric,
                        sweeprunner)
from repro.core.objectives import canonical_signs

SPEC = sweeprunner.SweepSpec(
    arches=("qwen1.5-0.5b",), mesh_shapes=((2, 2), (4, 1)),
    scenario="train", logic_nodes=("N7", "N5"),
    n_tilings=4, chunk_size=1)                 # 4 points, 4 chunks
LABELS = sweeprunner.enumerate_labels(SPEC)
CHUNKS = sweeprunner.make_chunks(LABELS, SPEC.chunk_size)
FP = SPEC.fingerprint()


def _fake_record(label, i):
    """A schema-shaped training row without touching the evaluator."""
    return {"key": f"k{i}", "arch": label.arch, "cell": label.cell,
            "mesh": "x".join(map(str, label.mesh)), "logic": label.logic,
            "hbm": label.hbm, "net": label.net, "scale": label.scale,
            "strategy": "RC-1-2-d2-p1", "devices": 4,
            "time_s": 1.0 + 0.25 * i, "compute_s": 0.5, "comm_s": 0.5,
            "exposed_comm_s": 0.25}


def _write_sweep_dir(out, n_chunks=4):
    """A committed sweep directory built by hand (no real evaluations)."""
    os.makedirs(out, exist_ok=True)
    sweepexec.write_spec_head(os.path.join(out, "spec.json"),
                              sweeprunner.SPEC_VERSION, FP, SPEC.to_dict())
    j = sweepexec.ChunkJournal(os.path.join(out, "results.jsonl"),
                               os.path.join(out, "checkpoint.jsonl")).open()
    for c in CHUNKS[:n_chunks]:
        j.commit(c.index, c.hash(FP),
                 [_fake_record(lab, c.index) for lab in c.labels])
    j.close()
    return out


# ---------------------------------------------------------- ingestion
def test_load_training_records_round_trip(tmp_path):
    out = _write_sweep_dir(str(tmp_path / "sw"))
    spec, records = surrogate.load_training_records(out)
    assert spec.fingerprint() == FP
    assert sorted(r["key"] for r in records) == ["k0", "k1", "k2", "k3"]
    assert all("chunk" not in r for r in records)


def test_load_training_records_tolerates_torn_final_line(tmp_path):
    """ISSUE-9 satellite: a writer killed mid-append leaves a torn final
    line in results.jsonl — training ingestion must keep every committed
    row and silently drop the tear, exactly like resume does."""
    out = _write_sweep_dir(str(tmp_path / "sw"))
    res = os.path.join(out, "results.jsonl")
    with open(res, "a") as fh:
        fh.write('{"chunk": 9, "key": "torn", "time_s": 0.0')  # no \n, cut
    _, records = surrogate.load_training_records(out)
    keys = sorted(r["key"] for r in records)
    assert keys == ["k0", "k1", "k2", "k3"]
    assert "torn" not in keys
    # a clean row of an UNcommitted chunk is filtered too (no done-line)
    with open(res, "a") as fh:
        fh.write('\n{"chunk": 9, "key": "uncommitted", "time_s": 1.0}\n')
    _, records = surrogate.load_training_records(out)
    assert "uncommitted" not in {r["key"] for r in records}


def test_dedupe_records_first_wins():
    rows = [{"key": "a", "v": 1}, {"key": "b", "v": 2}, {"key": "a", "v": 3}]
    out = surrogate.dedupe_records(rows)
    assert [r["v"] for r in out] == [1, 2]


# ------------------------------------------------------- featurize + fit
def test_featurizer_shapes_and_standardization():
    fz = surrogate.Featurizer.from_spec(SPEC, LABELS)
    X = fz.transform(SPEC, LABELS)
    assert X.shape == (len(LABELS), fz.dim)
    assert np.all(np.isfinite(X))
    # standardized over the full enumeration: roughly zero-mean columns
    assert np.abs(X.mean(axis=0)).max() < 1.0 + 1e-6


def test_fit_predict_sanity():
    records = [_fake_record(lab, i) for i, lab in enumerate(LABELS)]
    cfg = surrogate.SurrogateConfig(ensemble=2, hidden=8, steps=40)
    model = surrogate.fit_surrogate(SPEC, records, cfg=cfg)
    assert np.isfinite(model.loss)
    fz = model.featurizer
    mu, sigma, p = surrogate.predict(model, fz.transform(SPEC, LABELS))
    assert mu.shape == (len(LABELS), len(model.objectives))
    assert sigma.shape == mu.shape and np.all(sigma >= 0)
    assert p.shape == (len(LABELS),)
    assert np.all((p >= 0) & (p <= 1))
    assert np.all(np.isfinite(mu))


# ----------------------------------------------------------- hypervolume
def test_hypervolume_known_values():
    ref = np.array([1.0, 1.0])
    assert pathfinder.hypervolume(np.array([[0.0, 0.0]]), ref) \
        == pytest.approx(1.0)
    # two staircase points: union of rectangles, overlap not double-counted
    vals = np.array([[0.0, 0.5], [0.5, 0.0]])
    assert pathfinder.hypervolume(vals, ref) == pytest.approx(0.75)
    # dominated point adds nothing
    vals2 = np.vstack([vals, [0.6, 0.6]])
    assert pathfinder.hypervolume(vals2, ref) == pytest.approx(0.75)
    # points outside the reference box are clipped out entirely
    assert pathfinder.hypervolume(np.array([[2.0, 2.0]]), ref) == 0.0
    assert pathfinder.hypervolume(np.zeros((0, 2)), ref) == 0.0
    # 1-D: distance from the best value to the reference
    assert pathfinder.hypervolume(np.array([[0.25], [0.75]]),
                                  np.array([1.0])) == pytest.approx(0.75)
    # 3-D unit-cube corner
    assert pathfinder.hypervolume(np.array([[0.0, 0.0, 0.0]]),
                                  np.array([1.0, 1.0, 1.0])) \
        == pytest.approx(1.0)


# ----------------------------------------------------------- acquisition
def test_dominance_margin_and_empty_frontier():
    front = np.array([[0.0, 1.0], [1.0, 0.0]])
    z = np.array([[-0.5, -0.5],     # dominates both -> negative margin
                  [2.0, 2.0],       # dominated -> positive margin
                  [0.0, 1.0]])      # on the frontier -> zero
    m = surrogate.dominance_margin(z, front)
    assert m[0] < 0 and m[1] > 0 and m[2] == pytest.approx(0.0)
    empty = surrogate.dominance_margin(z, np.zeros((0, 2)))
    assert np.all(np.isneginf(empty))


@pytest.mark.parametrize("k", [1, 2, 3])
def test_acquisition_invariant_under_objective_sign_flips(k):
    """Property: UCB/EPI rankings must not change when an objective's
    orientation flips (maximize <-> minimize) — `canonical_signs` absorbs
    the sign, so acq(mu, front, signs) == acq(-mu_j, -front_j, -signs_j)
    exactly, for EVERY subset of flipped objectives and many draws."""
    rng = np.random.default_rng(1234 + k)
    for draw in range(25):
        n = int(rng.integers(1, 7))
        nf = int(rng.integers(1, 5))
        mu = rng.normal(size=(n, k))
        sigma = np.abs(rng.normal(size=(n, k)))
        front = rng.normal(size=(nf, k))
        signs = tuple(1.0 if i % 2 == 0 else -1.0 for i in range(k))
        for flip_mask in range(2 ** k):
            flips = np.array([-1.0 if flip_mask >> i & 1 else 1.0
                              for i in range(k)])
            mu2 = mu * flips
            front2 = front * flips
            signs2 = tuple(s * f for s, f in zip(signs, flips))
            for acq in (surrogate.ucb_acquisition,
                        surrogate.epi_acquisition):
                a = acq(mu, sigma, front, signs)
                b = acq(mu2, sigma, front2, signs2)
                np.testing.assert_allclose(a, b, rtol=1e-12, atol=1e-12)


def test_tied_chunk_ranking_is_permutation_independent():
    """Property: chunks with exactly equal scores come back in index
    order no matter how the input sequence was shuffled — the schedule is
    a pure function of (scores, identities), never of enumeration
    order."""
    import random
    chunks = list(CHUNKS)
    # duplicate score values force ties across several chunks
    vals = [0.5, 0.5, 1.5, 1.5, float("nan"), 0.5, 1.5, 0.5]
    scores = {c.index: vals[i % len(vals)]
              for i, c in enumerate(chunks)}
    want = [c.index for c in sweeprunner.order_chunks(chunks, scores)]
    rnd = random.Random(7)
    for _ in range(30):
        shuffled = list(chunks)
        rnd.shuffle(shuffled)
        got = [c.index for c in sweeprunner.order_chunks(shuffled, scores)]
        assert got == want
    # ties (and unscored/NaN chunks) are index-ascending within their band
    by_band = {}
    for c in sweeprunner.order_chunks(chunks, scores):
        s = scores.get(c.index)
        band = (s is None or not np.isfinite(s), s if s == s else 0.0)
        by_band.setdefault(band, []).append(c.index)
    for members in by_band.values():
        assert members == sorted(members)


def test_feasibility_weighted_pulls_unlikely_points_down():
    acq = np.array([3.0, 2.0, 1.0])
    p = np.array([0.0, 1.0, 1.0])
    w = surrogate.feasibility_weighted(acq, p)
    assert w[0] == pytest.approx(1.0)        # floored to the worst finite
    assert w[1] == pytest.approx(2.0) and w[2] == pytest.approx(1.0)


def test_chunk_scores_take_slice_max():
    spec = dataclasses.replace(SPEC, chunk_size=2)       # 2 chunks of 2
    chunks = sweeprunner.make_chunks(sweeprunner.enumerate_labels(spec), 2)
    scores = surrogate.chunk_scores(chunks,
                                    np.array([0.1, 0.9, 0.4, 0.2]))
    assert scores[chunks[0].index] == pytest.approx(0.9)
    assert scores[chunks[1].index] == pytest.approx(0.4)


# ------------------------------------------------- advisory chunk order
def test_write_load_chunk_order_round_trip(tmp_path):
    out = str(tmp_path)
    sweepfabric.write_chunk_order(out, [2, 0, 3, 1], FP)
    assert sweepfabric.load_chunk_order(out, FP, 4) == [2, 0, 3, 1]
    # fingerprint mismatch -> advisory file is ignored, not an error
    assert sweepfabric.load_chunk_order(out, "deadbeef", 4) is None
    # partial order: missing indices are appended ascending
    sweepfabric.write_chunk_order(out, [3, 1], FP)
    assert sweepfabric.load_chunk_order(out, FP, 4) == [3, 1, 0, 2]
    # corrupt JSON -> ignored
    with open(os.path.join(out, "order.json"), "w") as fh:
        fh.write('{"fingerprint": "' + FP + '", "order": [3, ')
    assert sweepfabric.load_chunk_order(out, FP, 4) is None
    # out-of-range / duplicate entries are dropped, not fatal — the
    # advisory order can only ever *reorder* the scan
    with open(os.path.join(out, "order.json"), "w") as fh:
        json.dump({"fingerprint": FP, "order": [2, 99, 2, -1]}, fh)
    assert sweepfabric.load_chunk_order(out, FP, 4) == [2, 0, 1, 3]
    # non-int entries -> ignored entirely
    with open(os.path.join(out, "order.json"), "w") as fh:
        json.dump({"fingerprint": FP, "order": [0, "x"]}, fh)
    assert sweepfabric.load_chunk_order(out, FP, 4) is None


def test_fabric_worker_scans_in_advisory_order(tmp_path):
    out = str(tmp_path / "fab")
    sweepfabric.init_dir(SPEC, out)
    w = sweepfabric.FabricWorker(out, worker_id="w0")
    assert [c.index for c in w._scan] == [0, 1, 2, 3]    # no order.json
    sweepfabric.write_chunk_order(out, [3, 1, 2, 0], FP)
    w = sweepfabric.FabricWorker(out, worker_id="w1")
    assert [c.index for c in w._scan] == [3, 1, 2, 0]
    # a stale advisory file (wrong fingerprint) falls back to index order
    sweepfabric.write_chunk_order(out, [3, 1, 2, 0], "deadbeef")
    w = sweepfabric.FabricWorker(out, worker_id="w2")
    assert [c.index for c in w._scan] == [0, 1, 2, 3]


def test_rank_chunks_and_order_fabric_dir(tmp_path):
    records = [_fake_record(lab, i) for i, lab in enumerate(LABELS)]
    cfg = surrogate.ExploreConfig(
        surrogate=surrogate.SurrogateConfig(ensemble=2, hidden=8,
                                            steps=30))
    order = surrogate.rank_chunks(SPEC, records, cfg=cfg)
    assert sorted(order) == [c.index for c in CHUNKS]
    out = str(tmp_path / "fab")
    sweepfabric.init_dir(SPEC, out)
    written = surrogate.order_fabric_dir(out, records, cfg=cfg)
    assert written == order
    assert sweepfabric.load_chunk_order(out, FP, len(CHUNKS)) == order


# ------------------------------------------------------- explore loop
def test_explore_budget_is_a_hard_ceiling(tmp_path):
    cfg = surrogate.ExploreConfig(
        eval_budget=2, init_chunks=1, batch_chunks=1, min_fit_rows=1,
        surrogate=surrogate.SurrogateConfig(ensemble=2, hidden=8,
                                            steps=30))
    stats = surrogate.explore(SPEC, out_dir=str(tmp_path / "ex"),
                              cfg=cfg, cache=None)
    assert stats.n_points_evaluated <= 2
    assert stats.stop == "budget"
    assert len(stats.records) == stats.n_points_evaluated


def test_explore_resume_skips_committed_chunks(tmp_path):
    out = str(tmp_path / "ex")
    cfg = surrogate.ExploreConfig(
        eval_budget=2, init_chunks=1, batch_chunks=1, min_fit_rows=1,
        surrogate=surrogate.SurrogateConfig(ensemble=2, hidden=8,
                                            steps=30))
    first = surrogate.explore(SPEC, out_dir=out, cfg=cfg, cache=None)
    assert first.n_points_evaluated == 2
    # an existing directory without resume=True must refuse, like sweep
    with pytest.raises(FileExistsError):
        surrogate.explore(SPEC, out_dir=out, cfg=cfg, cache=None)
    cfg2 = dataclasses.replace(cfg, eval_budget=len(LABELS))
    second = surrogate.explore(SPEC, out_dir=out, cfg=cfg2, resume=True,
                               cache=None)
    # the budget is per-invocation and committed chunks never re-run
    assert second.n_chunks_skipped == first.n_chunks_evaluated
    assert second.n_points_evaluated == len(LABELS) - 2
    assert second.stop == "exhausted"
    keys = sorted(r["key"] for r in second.records)
    assert len(keys) == len(set(keys)) == len(LABELS)
    # the explored directory is a normal sweep directory
    spec2, records2 = sweeprunner.load_sweep(out)
    assert spec2.fingerprint() == FP and len(records2) == len(LABELS)


def test_explore_frontier_matches_exhaustive_on_tiny_grid(tmp_path):
    """With the budget == the grid, explore IS the exhaustive sweep."""
    cfg = surrogate.ExploreConfig(
        eval_budget=len(LABELS), init_chunks=2, batch_chunks=2,
        min_fit_rows=2,
        surrogate=surrogate.SurrogateConfig(ensemble=2, hidden=8,
                                            steps=30))
    stats = surrogate.explore(SPEC, cfg=cfg, cache=None)
    assert stats.n_points_evaluated == len(LABELS)
    full = sweeprunner.SweepRunner(SPEC, cache=None).run()
    scn = SPEC.scenario_spec.variants()[0].resolve()
    want = sorted(r["key"] for r in sweeprunner.pareto_records(
        full.records, scn.objectives))
    got = sorted(r["key"] for r in stats.frontier)
    assert got == want
