"""Whether a run's answers are correct, judged against `bench.reference`.

Each check returns the numbers it compared, each with its limit.  A run is
correct when every number is within its limit.  The records checked are a
sample drawn from the seed once the window has closed; what every request
of the window owes (its whole set of points, an exact frontier) is checked
for every request.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Mapping, Sequence

import jax

from bench import generator
from bench.reference import (REF_RTOL, Label, Reference, compare_records,
                             dominated_within, pareto)

# records compared with the reference in a sweep run, queries in a query
# run, and points of a frontier run that the frontier has to cover
N_RECORDS = 24
N_QUERIES = 3
N_COVER = 12


def plan_summary(plan) -> Dict:
    """A sizing answer as plain data (the program's or the reference's)."""
    best = plan.best
    return {"n_sized": int(plan.n_sized),
            "n_unsizeable": int(plan.n_unsizeable),
            "best": None if best is None else [best.key, int(best.devices),
                                               int(best.replicas)],
            "candidates": [[c.key, int(c.devices), int(c.replicas)]
                           for c in plan.candidates]}


class Numbers:
    """The numbers a check compared, each with its limit."""

    def __init__(self):
        self.items: Dict[str, List[float]] = {}

    def put(self, name: str, value: float, limit: float) -> None:
        self.items[name] = [float(value), float(limit)]

    @property
    def correct(self) -> bool:
        return all(v <= lim for v, lim in self.items.values())

    def lines(self) -> List[str]:
        return [f"check {k}: {v!r} (limit {lim!r})"
                for k, (v, lim) in self.items.items()]


def read_jsonl(path: str) -> List[Dict]:
    if not os.path.exists(path):
        return []
    with open(path) as fh:
        return [json.loads(ln) for ln in fh if ln.strip()]


def _sample(rng, items: Sequence, n: int) -> list:
    if len(items) <= n:
        return list(items)
    idx = rng.choice(len(items), size=n, replace=False)
    return [items[int(i)] for i in sorted(idx)]


def _compare(ref: Reference, recs: Sequence[Mapping], out: Numbers,
             stand_in=None) -> None:
    worst, wrong = 0.0, 0
    for r in recs:
        lb = Label.of(r)
        d, n = compare_records(stand_in(lb) if stand_in else r,
                               ref.record(lb))
        worst, wrong = max(worst, d), wrong + n
    out.put("record_rel_diff", worst, REF_RTOL)
    out.put("record_fields_wrong", wrong, 0)


def check_sweeps(ref: Reference, done: Sequence[Mapping], seed: int,
                 stand_in=None) -> Numbers:
    """Full-record sweeps: every point of every sweep read back from its
    results file, once; a sample compared with the reference.

    ``stand_in(label) -> record``, where given, answers in the program's
    place for the sampled records (the control)."""
    out = Numbers()
    rng = generator.rng_for(seed, 1)
    missing, rows = 0, []
    for d in done:
        want = ref.keys(d["req"]["scales"])
        got = read_jsonl(os.path.join(d["out_dir"], "results.jsonl"))
        keys = [r.get("key") for r in got]
        missing += len(set(want) ^ set(keys)) + (len(keys) - len(set(keys)))
        rows.extend(got)
    out.put("points_missing_or_extra", missing, 0)
    with jax.default_device(jax.devices("cpu")[0]):
        _compare(ref, _sample(rng, rows, N_RECORDS), out, stand_in)
    return out


def check_frontiers(ref: Reference, done: Sequence[Mapping], seed: int,
                    objectives: Sequence[str],
                    full_records: Sequence[Mapping],
                    stand_in=None) -> Numbers:
    """Frontier sweeps: each folded every point of its grid with nothing
    dropped; its frontier records agree with the reference and dominate
    none of each other; a sample of the grid's points is covered by the
    frontier; and the last sweep's frontier is the Pareto filter of the
    same sweep's full records (``full_records``)."""
    out = Numbers()
    rng = generator.rng_for(seed, 1)
    missed, bad, front_recs, uncovered = 0, 0, [], 0
    with jax.default_device(jax.devices("cpu")[0]):
        for d in done:
            want = ref.keys(d["req"]["scales"])
            missed += abs(d["points"] - len(want)) + d["overflowed"]
            front = d["frontier"] or []
            keys = [r.get("key") for r in front]
            bad += int(not front) + len(set(keys) - set(want)) \
                + (len(keys) - len(set(keys))) \
                + (len(front) - len(pareto(front, objectives)))
            front_recs.extend(front)
        _compare(ref, _sample(rng, front_recs, N_RECORDS), out, stand_in)
        # points spread over the window's sweeps
        picks = _sample(rng, [(i, k) for i, d in enumerate(done)
                              for k in ref.keys(d["req"]["scales"])],
                        N_COVER)
        for i, key in picks:
            rec = ref.record(label_of_key(key))
            uncovered += int(not dominated_within(
                rec, done[i]["frontier"] or [], objectives, REF_RTOL))
    last = {r["key"] for r in done[-1]["frontier"] or []}
    full = {r["key"] for r in pareto(full_records, objectives)}
    out.put("frontier_points_missed", missed, 0)
    out.put("frontier_malformed", bad, 0)
    out.put("frontier_uncovered_points", uncovered, 0)
    out.put("frontier_vs_full_sweep", len(last ^ full), 0)
    return out


def check_queries(ref: Reference, done: Sequence[Mapping], seed: int,
                  slo: Mapping[str, float], stand_in=None) -> Numbers:
    """Sizing queries: a sample of the window's queries, each with every
    record it evaluated and its sizing answer recomputed by the
    reference."""
    out = Numbers()
    rng = generator.rng_for(seed, 1)
    worst, wrong, missing, answers = 0.0, 0, 0, 0
    with jax.default_device(jax.devices("cpu")[0]):
        for d in _sample(rng, list(done), N_QUERIES):
            want = {k: ref.record(label_of_key(k))
                    for k in ref.keys(d["req"]["scales"])}
            got = d["records"]
            answer = d["plan"]
            if stand_in is not None:
                got = [stand_in(label_of_key(k)) for k in want]
                answer = plan_summary(ref.size(got, d["req"]["qps"], slo))
            missing += len(set(want) ^ {r["key"] for r in got})
            for r in got:
                if r["key"] in want:
                    dd, n = compare_records(r, want[r["key"]])
                    worst, wrong = max(worst, dd), wrong + n
            plan = ref.size(list(want.values()), d["req"]["qps"], slo)
            answers += int(plan_summary(plan) != answer)
    out.put("record_rel_diff", worst, REF_RTOL)
    out.put("record_fields_wrong", wrong, 0)
    out.put("points_missing_or_extra", missing, 0)
    out.put("sizing_answers_wrong", answers, 0)
    return out


def label_of_key(key: str) -> Label:
    arch, cell, mesh, logic, hbm, net, scale, strategy = key.split("|")
    return Label(arch=arch, cell=cell,
                 mesh=tuple(int(x) for x in mesh.split("x")), logic=logic,
                 hbm=hbm, net=net, scale=float(scale), strategy=strategy)
