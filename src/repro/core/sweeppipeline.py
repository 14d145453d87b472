"""Pipelined device-resident sweep executor — the 10^6-point hot path.

`sweeprunner.eval_labels` resolves labels, packs hardware vectors, runs the
batched evaluator and folds records in ONE synchronous loop per chunk, so a
sweep alternates host-side Python with device compute and JSONL writes on
the critical path.  This module rebuilds that hot path as an asynchronous,
double-buffered pipeline (`SweepRunner(backend="pipeline")`):

  * a **producer thread** resolves and packs chunk N+1 while chunk N runs:
    per-label work is reduced to dict lookups — `resolve_label` skeletons
    (scenario, parsed strategy, system graph, workload graphs, compiled-fn
    keys, record templates) are memoized per (arch, cell, mesh, strategy),
    AGE'd-and-packed hardware rows per (logic, hbm, net, scale) in a
    process-global row cache — and prediction-cache probes are batched
    into one locked pass (`PredictionCache.get_many`); the `(B, HW_DIM)`
    miss matrix is a NumPy gather over unique rows, never a per-label
    Python pack;
  * the **device stage** dispatches consecutive chunks as one *superbatch*
    under JAX async dispatch: all eval points of a design are fused into a
    single compiled per-skeleton function (a serving design's prefill and
    decode graphs cost one dispatch, not two), block-padded so successive
    packs reuse a handful of compiled shapes, and `jax.pmap`-sharded
    row-wise when the batch is large enough to amortize pmap's dispatch
    cost (below that, one jitted call keeps XLA's intra-op parallelism);
  * a **writer thread** blocks on chunk N-1's device buffers, folds
    records through the scenario's `metrics_fold` fast path and commits
    JSONL rows + checkpoint lines off the critical path, preserving chunk
    order — `resume` semantics are byte-identical to the synchronous
    backends (a crash loses at most the in-flight superbatches).

`run_frontier` is the device-resident reduction mode behind ``pathfind
sweep --frontier-only``: the scenario's objective fold
(`Scenario.frontier_fold`) and a streaming Pareto merge
(`pathfinder.frontier_merge`) are fused INTO the compiled eval fn with the
carried frontier state donated between calls, so a 10^6-point sweep pulls
only the surviving frontier (plus its raw metric rows) to host — full
per-point rows never materialize.

`benchmarks/sweep_pipeline.py` asserts the throughput gain over the PR4
synchronous sharded path and the frontier/full-materialization parity.
"""

from __future__ import annotations

import collections
import dataclasses
import json
import os
import queue
import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config
from repro.core import pathfinder, scenarios
from repro.core.parallelism import Strategy
from repro.core.placement import mesh_system

# design points per device dispatch: consecutive chunks are packed into one
# superbatch so per-dispatch overhead amortizes over ~10x more points than
# the default chunk size (commit granularity stays per chunk)
SUPERBATCH = 256
# superbatches packed (and AOT-submitted) ahead of the device stage: while
# the device runs superbatch N, the producer has already handed N+1..N+k's
# compiled-fn keys and padded shapes to the compile service, so a cold
# sweep's XLA compiles run off the critical path (see repro.core
# .compileahead).  0 disables lookahead (and AOT prefetch) entirely.
COMPILE_AHEAD = 2
# packed-superbatch lookahead per queue (producer -> device -> writer):
# 2 = double buffering at each stage boundary
QUEUE_DEPTH = 2
# minimum per-group batch before the pmap-sharded path pays for itself: a
# pmap dispatch costs milliseconds of host-side argument sharding, while a
# jitted call still uses every core through XLA's intra-op parallelism
PMAP_MIN_ROWS = 1024

# process-global packed-hardware rows, keyed like `sweeprunner._HW_CACHE`
# (tech axis + budget overrides + profile digest).  A pack's fresh rows
# go through AGE in one batched call (`_prime_rows`); caching the packed
# rows per process instead of per run keeps the producer's per-label
# cost at dict-lookup speed.  LRU-capped: each entry pins a
# MicroArch, and a long-lived process sweeping many tech/scale/profile
# axes must not grow it forever (same treatment as roofline._GEMM_CACHE).
_ROW_CACHE: "collections.OrderedDict[tuple, tuple]" = \
    collections.OrderedDict()
_ROW_CACHE_MAXSIZE = 4096
_ROW_LOCK = threading.Lock()


def _row_cache_get(key) -> Optional[tuple]:
    with _ROW_LOCK:
        ent = _ROW_CACHE.get(key)
        if ent is not None:
            _ROW_CACHE.move_to_end(key)
        return ent


def _row_cache_put(key, ent: tuple) -> tuple:
    with _ROW_LOCK:
        ent = _ROW_CACHE.setdefault(key, ent)
        _ROW_CACHE.move_to_end(key)
        while len(_ROW_CACHE) > _ROW_CACHE_MAXSIZE:
            _ROW_CACHE.popitem(last=False)
        return ent


def _wait_pack(pack_q: "queue.Queue"):
    """The dispatcher's blocking read of the next packed superbatch."""
    with jax.profiler.TraceAnnotation("repro.pipeline.wait_pack"):
        return pack_q.get()


def _join_producer(producer: threading.Thread, pack_q: "queue.Queue"):
    """Join the producer, draining its bounded queue while waiting.

    An exception that escapes the consumer loop (KeyboardInterrupt landing
    outside the inner try) leaves the producer blocked in a `put()` on the
    full queue with nobody reading; a bare `join()` would then hang
    forever.  Draining between join attempts unblocks it, and the
    producer's own error check / sentinel path finishes it off.
    """
    while True:
        producer.join(timeout=0.1)
        if not producer.is_alive():
            return
        try:
            while True:
                pack_q.get_nowait()
        except queue.Empty:
            pass


@dataclasses.dataclass
class _DesignSkeleton:
    """Everything shared by labels of one (arch, cell, mesh, strategy):
    resolved once, then every label in the cell is a pair of dict hits."""

    scn: scenarios.Scenario
    cfg: object
    strategy: Strategy
    system: object
    graphs: Tuple
    evaluators: Tuple[pathfinder.BatchedEvaluator, ...]
    fold: Optional[Callable]         # device frontier-objective fold
    mfold: Optional[Callable]        # host metric fold (record fast path)
    base_fields: Dict                # record template (label-field order)
    key_pre: str                     # "arch|cell|mesh" of point_key
    key_suf: str                     # strategy part of point_key
    # scenario identity (spec params + cell variant) baked into fold/mfold;
    # groups and the frontier compile cache must not mix fold_keys even
    # when the eval-shape keys coincide (variants share graphs, not walls)
    fold_key: tuple = ()
    # systolic_dims -> per-eval-point compiled-skeleton key tuple
    skel_keys: Dict[tuple, tuple] = dataclasses.field(default_factory=dict)

    @property
    def ppd(self) -> int:
        return len(self.graphs)


@dataclasses.dataclass
class _Group:
    """One compiled-function batch inside a pack: all miss labels sharing
    a design skeleton + systolic dims."""

    skel: _DesignSkeleton
    keys: tuple                      # per-eval-point skeleton keys
    template: object                 # MicroArch supplying static leaves
    ridx: List[int] = dataclasses.field(default_factory=list)
    row_bytes: List[bytes] = dataclasses.field(default_factory=list)
    slots: List[tuple] = dataclasses.field(default_factory=list)
    gidx: List[int] = dataclasses.field(default_factory=list)
    out: object = None               # in-flight device result
    n: int = 0


@dataclasses.dataclass
class _Pack:
    """One packed superbatch: chunks + per-label resolution + cache hits
    + compiled-batch groups (built by the producer stage)."""

    chunks: List
    meta: List[List]                 # [ci][li] -> (skel, hw entry)
    cached: Dict[tuple, np.ndarray]  # (ci, li) -> (ppd, 5) f64 rows
    groups: Dict[tuple, _Group]


@dataclasses.dataclass
class _BucketOut:
    """One in-flight bucketed megabatch result, shared by every (group,
    eval-point) slice that rode in it; materialized to host once."""

    out: object                      # device array, (B, 5) or (D, B/D, 5)
    _host: Optional[np.ndarray] = None

    def rows(self) -> np.ndarray:
        if self._host is None:
            host = np.asarray(self.out, dtype=np.float64)
            self._host = host.reshape(-1, host.shape[-1])
            self.out = None
        return self._host


class PipelineExecutor:
    """Asynchronous producer -> device -> writer pipeline for one spec.

    One instance per `SweepRunner.run` call; all memoization (skeletons,
    packed hardware rows, compiled functions via the process-wide
    `pathfinder._COMPILED` LRU) is keyed so repeated runs stay warm.
    """

    def __init__(self, spec, cache=pathfinder.DEFAULT_CACHE,
                 superbatch: int = SUPERBATCH,
                 devices: Optional[int] = None,
                 threads: Optional[bool] = None,
                 compile_ahead: Optional[int] = None,
                 bucketing: Optional[bool] = None):
        from repro.core import compileahead, sweeprunner
        self.spec = spec
        self.cache = pathfinder.resolve_cache(cache)
        self.ppe = sweeprunner.spec_ppe(spec)
        self.superbatch = max(int(superbatch), spec.chunk_size, 1)
        self.devices = devices if devices is not None \
            else jax.local_device_count()
        # producer/writer threads only pay off when the host has spare
        # cores for them: on <=3 cores the GIL serializes the Python
        # stages anyway and thread churn just fights XLA's own pool, so
        # the inline mode double-buffers through JAX async dispatch alone
        self.threads = threads if threads is not None \
            else (os.cpu_count() or 1) >= 4
        self.compile_ahead = COMPILE_AHEAD if compile_ahead is None \
            else max(int(compile_ahead), 0)
        self.bucketing = compileahead.resolve_bucketed(bucketing)
        self.block = sweeprunner.SHARD_BLOCK
        self._skels: Dict[tuple, _DesignSkeleton] = {}
        self._scn_fp = json.dumps(spec.scenario_spec.to_dict(),
                                  sort_keys=True)
        self._hw: Dict[tuple, tuple] = {}
        self._rows: List[np.ndarray] = []     # unique packed hw rows
        self._rowmat: Optional[np.ndarray] = None
        # store keys the AOT service pinned on our behalf (see _prefetch);
        # the device stage releases a key's pins after its first dispatch
        self._aot_pins: "collections.Counter" = collections.Counter()
        self._pin_lock = threading.Lock()
        self._frontier_capacity: Optional[int] = None

    # -- memoized resolution ---------------------------------------------
    def _hw_entry(self, lb) -> tuple:
        """(hw arch, row index, row bytes, scale string) of one label."""
        hkey = (lb.logic, lb.hbm, lb.net, lb.scale)
        ent = self._hw.get(hkey)
        if ent is None:
            self._prime_rows((lb,))
            ent = self._hw[hkey]
        return ent

    def _prime_rows(self, labels) -> None:
        """Register the hardware rows of ``labels`` that this executor
        lacks; those the process row cache lacks too go through AGE
        together, in one `sweeprunner._hardware_many` call."""
        from repro.core import sweeprunner
        tail = (self.spec.area_mm2, self.spec.power_w,
                sweeprunner._profile_key(self.spec))
        fresh = []
        for hkey in dict.fromkeys((lb.logic, lb.hbm, lb.net, lb.scale)
                                  for lb in labels):
            if hkey in self._hw:
                continue
            cached = _row_cache_get(hkey + tail)
            if cached is None:
                fresh.append(hkey)
            else:
                self._add_row(hkey, cached)
        for hkey, hw in zip(fresh,
                            sweeprunner._hardware_many(self.spec, fresh)):
            row = pathfinder.pack_hw(hw)
            self._add_row(hkey, _row_cache_put(
                hkey + tail, (hw, row, row.tobytes(), f"{hkey[3]:g}")))

    def _add_row(self, hkey: tuple, cached: tuple) -> None:
        hw, row, rbytes, scale_str = cached
        self._hw[hkey] = (hw, len(self._rows), rbytes, scale_str)
        self._rows.append(row)
        self._rowmat = None

    def _skeleton(self, lb) -> _DesignSkeleton:
        from repro.core import sweeprunner
        skey = (lb.arch, lb.cell, lb.mesh, lb.strategy)
        sk = self._skels.get(skey)
        if sk is None:
            hw = self._hw_entry(lb)[0]
            scn = sweeprunner.scenario_for(self.spec, lb.cell)
            cfg = get_config(lb.arch)
            st = Strategy.parse(lb.strategy)
            system = mesh_system(lb.mesh)
            dp = scenarios.DesignPoint(
                arch=lb.arch, cell=lb.cell, mesh=lb.mesh, logic=lb.logic,
                hbm=lb.hbm, net=lb.net, scale=lb.scale, strategy=st,
                cfg=cfg, hw=hw, system=system)
            eps = scn.eval_points(dp)
            evs = tuple(pathfinder.BatchedEvaluator(
                ep.graph, st, system=ep.system, ppe=self.ppe,
                pod_bw=ep.pod_bw, cache=None) for ep in eps)
            name = st.name
            mesh_str = "x".join(map(str, lb.mesh))
            base = {"arch": lb.arch, "cell": lb.cell, "mesh": mesh_str,
                    "logic": None, "hbm": None, "net": None, "scale": None,
                    "strategy": name, "devices": st.devices}
            sk = _DesignSkeleton(
                scn=scn, cfg=cfg, strategy=st, system=system,
                graphs=tuple(ep.graph for ep in eps), evaluators=evs,
                fold=scn.frontier_fold(cfg, st),
                mfold=scn.metrics_fold(cfg, st, lb.cell),
                base_fields=base,
                key_pre=f"{lb.arch}|{lb.cell}|{mesh_str}", key_suf=name,
                fold_key=(self._scn_fp, lb.cell))
            self._skels[skey] = sk
        return sk

    def _group_keys(self, sk: _DesignSkeleton, hw) -> tuple:
        sd = tuple(hw.tech.compute.systolic_dims)
        keys = sk.skel_keys.get(sd)
        if keys is None:
            keys = tuple(ev._skeleton(hw) for ev in sk.evaluators)
            sk.skel_keys[sd] = keys
        return keys

    def _design_point(self, lb, sk: _DesignSkeleton,
                      hw) -> scenarios.DesignPoint:
        return scenarios.DesignPoint(
            arch=lb.arch, cell=lb.cell, mesh=lb.mesh, logic=lb.logic,
            hbm=lb.hbm, net=lb.net, scale=lb.scale, strategy=sk.strategy,
            cfg=sk.cfg, hw=hw, system=sk.system)

    # -- compiled functions ----------------------------------------------
    def _design_scalar(self, group: _Group) -> Callable:
        """v (HW_DIM,) -> (ppd, 5) metric rows: every eval point of one
        design fused into a single traced function."""
        scalars = [ev._scalar_fn(group.template)
                   for ev in group.skel.evaluators]

        def design(v):
            return jnp.stack([f(v) for f in scalars])
        return design

    def _eval_build(self, group: _Group, n_dev: int) -> Callable:
        if n_dev > 1:
            return lambda: jax.pmap(jax.vmap(self._design_scalar(group)))
        return lambda: jax.jit(jax.vmap(self._design_scalar(group)))

    def _compiled_eval(self, group: _Group, n_dev: int) -> Callable:
        key = ("design", group.keys, n_dev)
        return pathfinder.compiled_entry(key, self._eval_build(group, n_dev))

    def _design_vectors(self, group: _Group) -> List:
        """One canonical `DesignVector` per eval point of the group's
        design, registered under the same per-evaluator skeleton keys the
        serial backend uses — so serial and pipelined sweeps share (and
        bit-match) the exact same bucket executables."""
        from repro.core import compileahead
        avals = (jax.ShapeDtypeStruct((pathfinder.HW_DIM,), jnp.float32),)
        return [compileahead.design_vector(
                    ("skel", key),
                    lambda ev=ev: ev._scalar_fn(group.template), avals)
                for key, ev in zip(group.keys, group.skel.evaluators)]

    def _frontier_build(self, group: _Group, capacity: int) -> Callable:
        def build():
            design = self._design_scalar(group)
            fold = group.skel.fold

            def step(hw, idx, state):
                rows = jax.vmap(design)(hw)                  # (B, ppd, 5)
                vals = jax.vmap(fold)(rows, hw)              # (B, n_obj)
                vals = jnp.where((idx < 0)[:, None], jnp.inf, vals)
                payload = rows.reshape(rows.shape[0], -1)
                return pathfinder.frontier_merge(state, vals, payload, idx)
            # the carried frontier state is donated: chunk N's merge reuses
            # chunk N-1's buffers instead of allocating a fresh state
            return jax.jit(step, donate_argnums=2)
        return build

    def _compiled_frontier(self, group: _Group, capacity: int) -> Callable:
        # fold_key matters here: the objective fold (SLO walls, traffic
        # consts) is traced into the step, unlike the pure eval fn
        key = ("frontier", group.keys, group.skel.fold_key, capacity)
        return pathfinder.compiled_entry(
            key, self._frontier_build(group, capacity))

    # -- packing (producer side) -----------------------------------------
    def pack(self, chunks: Sequence) -> _Pack:
        """Resolve + vectorize one superbatch of chunks: memoized skeleton
        and hardware-row lookups per label, one batched cache probe, and
        miss row-indices grouped per compiled function."""
        self._prime_rows(lb for chunk in chunks for lb in chunk.labels)
        meta: List[List] = []
        cached: Dict[tuple, np.ndarray] = {}
        groups: Dict[tuple, _Group] = {}
        chunk_size = self.spec.chunk_size

        def group_for(sk, hw):
            # group identity includes the scenario fold_key: variants share
            # eval shapes (g.keys, so the compiled eval fn and cache rows
            # stay shared) but their folds bake different walls/consts
            keys = self._group_keys(sk, hw)
            gkey = (keys, sk.fold_key)
            g = groups.get(gkey)
            if g is None:
                g = groups.setdefault(gkey, _Group(skel=sk, keys=keys,
                                                   template=hw))
            return g

        if self.cache is None:          # lean single-pass (no probes)
            for ci, chunk in enumerate(chunks):
                base_gidx = chunk.index * chunk_size
                row_meta = []
                meta.append(row_meta)
                for li, lb in enumerate(chunk.labels):
                    ent = self._hw_entry(lb)
                    sk = self._skeleton(lb)
                    row_meta.append((sk, ent))
                    g = group_for(sk, ent[0])
                    g.ridx.append(ent[1])
                    g.slots.append((ci, li))
                    g.gidx.append(base_gidx + li)
            return _Pack(chunks=list(chunks), meta=meta, cached=cached,
                         groups=groups)

        probe_keys: List[tuple] = []
        probe_slots: List[tuple] = []
        pending: List[tuple] = []       # (slot, gidx, sk, ent)
        for ci, chunk in enumerate(chunks):
            base_gidx = chunk.index * chunk_size
            row_meta = []
            meta.append(row_meta)
            for li, lb in enumerate(chunk.labels):
                ent = self._hw_entry(lb)
                sk = self._skeleton(lb)
                slot = (ci, li)
                row_meta.append((sk, ent))
                pending.append((slot, base_gidx + li, sk, ent))
                for skel_key in self._group_keys(sk, ent[0]):
                    probe_keys.append((skel_key, ent[2]))
                    probe_slots.append(slot)
        hits: Dict[tuple, List] = {}
        for slot, row in zip(probe_slots,
                             self.cache.get_many(probe_keys)):
            hits.setdefault(slot, []).append(row)
        for slot, gidx, sk, ent in pending:
            got = hits.get(slot)
            if got is not None and all(r is not None for r in got):
                cached[slot] = np.stack(got)
                continue
            hw, ridx, rbytes, _ = ent
            g = group_for(sk, hw)
            g.ridx.append(ridx)
            g.row_bytes.append(rbytes)
            g.slots.append(slot)
            g.gidx.append(gidx)
        return _Pack(chunks=list(chunks), meta=meta, cached=cached,
                     groups=groups)

    # -- device stage -----------------------------------------------------
    def _gather(self, g: _Group) -> np.ndarray:
        """(B, HW_DIM) f32 matrix of a group's rows — one NumPy gather
        over the unique-row table, no per-label packing.

        Runs on the dispatch thread while the producer may be appending
        rows for the NEXT pack, so work off a local snapshot: every index
        this group references existed when the pack was built, and a
        concurrent append can only grow the table past what we need.
        """
        idx = np.asarray(g.ridx, dtype=np.intp)
        mat = self._rowmat
        need = int(idx.max()) + 1 if idx.size else 0
        if mat is None or mat.shape[0] < need:
            mat = np.stack(self._rows[:max(need, len(self._rows))]) \
                .astype(np.float32)
            self._rowmat = mat
        return mat[idx]

    def _pad_plan(self, n: int) -> Tuple[int, int]:
        """(n_dev, padded row target) for an ``n``-row dispatch."""
        n_dev = max(min(self.devices, n), 1)
        if n < PMAP_MIN_ROWS:
            n_dev = 1                 # jit + XLA intra-op parallelism
        quantum = n_dev * self.block
        return n_dev, -(-n // quantum) * quantum

    def _padded(self, g: _Group) -> Tuple[np.ndarray, int]:
        hw = self._gather(g)
        n = hw.shape[0]
        n_dev, target = self._pad_plan(n)
        if target != n:
            hw = np.concatenate([hw, np.repeat(hw[-1:], target - n,
                                               axis=0)])
        return hw, n_dev

    def _release_pins(self, key: tuple) -> None:
        """Release the LRU-eviction pins the AOT service took for ``key``
        (called after the key's first dispatch of this run)."""
        with self._pin_lock:
            n = self._aot_pins.pop(key, 0)
        for _ in range(n):
            pathfinder.unpin_compiled(key)

    def _release_all_pins(self) -> None:
        with self._pin_lock:
            pins, self._aot_pins = self._aot_pins, collections.Counter()
        for key, n in pins.items():
            for _ in range(n):
                pathfinder.unpin_compiled(key)

    def _bucket_plan(self, pack: _Pack) -> Dict[int, tuple]:
        """Group the pack's (group, eval-point) pairs by canonical bucket.

        Returns ``{bucket.id: (bucket, items)}`` with items
        ``(group, eval_idx, design_vector, n_rows)`` — the shared shape
        plan used by both `_prefetch` (AOT submit) and `dispatch`.
        """
        buckets: Dict[int, tuple] = {}
        for g in pack.groups.values():
            n = len(g.ridx)
            if not n:
                continue
            for e, dv in enumerate(self._design_vectors(g)):
                buckets.setdefault(dv.bucket.id, (dv.bucket, []))[1] \
                    .append((g, e, dv, n))
        return buckets

    @staticmethod
    def _bucket_args(bucket, rows: np.ndarray, didx: np.ndarray,
                     packs_by_item: List[tuple], n_dev: int) -> tuple:
        """Assemble one megabatch's argument tuple: per-row coefficient
        packs (gathered from the per-item design vectors) + the hardware
        rows, reshaped with a leading device axis when pmap-sharded."""
        packs = tuple(
            np.stack([p[c] for p in packs_by_item])[didx]
            for c in range(len(bucket.classes)))
        if n_dev > 1:
            per = rows.shape[0] // n_dev
            rows = rows.reshape(n_dev, per, rows.shape[1])
            packs = tuple(p.reshape((n_dev, per) + p.shape[1:])
                          for p in packs)
        return (packs, rows)

    def dispatch(self, pack: _Pack) -> None:
        """Launch every group's fused eval under JAX async dispatch; the
        results stay on device until `finalize` folds them.

        With bucketing (default) all (group, eval-point) pairs whose
        canonical jaxprs landed in one bucket are dispatched as a single
        megabatch through the shared bucket executable — O(shape-buckets)
        compiles per pack instead of O(designs); per-design coefficient
        packs ride along as batch inputs, so records stay bit-identical
        to per-group dispatch of the same executables."""
        from repro.core import compileahead
        if not self.bucketing:
            for g in pack.groups.values():
                g.n = len(g.ridx)
                if not g.n:
                    continue
                hw, n_dev = self._padded(g)
                fn = self._compiled_eval(g, n_dev)
                if n_dev > 1:
                    g.out = fn(jnp.asarray(
                        hw.reshape(n_dev, hw.shape[0] // n_dev,
                                   pathfinder.HW_DIM)))
                else:
                    g.out = fn(jnp.asarray(hw))
                self._release_pins(("design", g.keys, n_dev))
            return
        for g in pack.groups.values():
            g.n = len(g.ridx)
            if g.n:
                g.out = [None] * g.skel.ppd
        for bucket, items in self._bucket_plan(pack).values():
            rows = np.concatenate([self._gather(g) for g, _, _, _ in items])
            didx = np.concatenate([np.full(n, j, dtype=np.intp)
                                   for j, (_, _, _, n) in enumerate(items)])
            n = rows.shape[0]
            n_dev, target = self._pad_plan(n)
            if target != n:
                rows = np.concatenate(
                    [rows, np.repeat(rows[-1:], target - n, axis=0)])
                didx = np.concatenate(
                    [didx, np.repeat(didx[-1:], target - n)])
            packs, hw = self._bucket_args(
                bucket, rows, didx, [dv.packs for _, _, dv, _ in items],
                n_dev)
            entry = compileahead.batch_entry(bucket, n_dev)
            out = entry(packs, jnp.asarray(hw))
            self._release_pins(("cabucket", bucket.id, n_dev))
            holder = _BucketOut(out=out)
            off = 0
            for g, e, _, n_g in items:
                g.out[e] = (holder, off, off + n_g)
                off += n_g

    def finalize(self, pack: _Pack) -> List[List[Dict]]:
        """Block on the pack's device results, fold records per chunk (in
        chunk order), and publish the fresh rows to the prediction cache
        under the same per-eval-point keys the synchronous backends use.

        Metric folding is vectorized: each group's whole result batch
        goes through the scenario's `metrics_fold` in one NumPy pass, so
        the per-label Python is one dict merge + the point key."""
        md_store: List[List] = [[None] * len(c.labels)
                                for c in pack.chunks]
        rows_by_slot: Dict[tuple, np.ndarray] = {}
        puts: List[tuple] = []
        n_metrics = len(pathfinder.METRICS)
        for g in pack.groups.values():
            if not g.n:
                continue
            if isinstance(g.out, list):
                # bucketed: one (B, 5) slice per eval point, possibly from
                # different megabatches; stack to the (B, ppd, 5) layout
                out = np.stack(
                    [holder.rows()[lo:hi] for holder, lo, hi in g.out],
                    axis=1)
            else:
                out = np.asarray(g.out, dtype=np.float64)
                out = out.reshape(-1, g.skel.ppd, n_metrics)[:g.n]
            g.out = None
            if g.skel.mfold is not None:
                for (ci, li), md in zip(g.slots,
                                        g.skel.mfold(out,
                                                     self._gather(g))):
                    md_store[ci][li] = md
            else:
                for j, slot in enumerate(g.slots):
                    rows_by_slot[slot] = out[j]
            if self.cache is not None:
                for j in range(g.n):
                    for pt, skel_key in enumerate(g.keys):
                        puts.append(((skel_key, g.row_bytes[j]),
                                     out[j, pt]))
        if puts:
            self.cache.put_many(puts)
        if pack.cached:
            # cache-hit slots: batch them per skeleton through the same
            # vectorized fold (a fully-warm sweep is all hits)
            by_sk: Dict[int, tuple] = {}
            for slot, rows in pack.cached.items():
                sk, ent = pack.meta[slot[0]][slot[1]]
                if sk.mfold is None:
                    rows_by_slot[slot] = rows
                else:
                    by_sk.setdefault(id(sk), (sk, []))[1].append(
                        (slot, rows, ent[1]))
            for sk, items in by_sk.values():
                rows = np.stack([r for _, r, _ in items])
                hwm = np.stack([self._rows[ri] for _, _, ri in items])
                for ((ci, li), _, _), md in zip(items,
                                                sk.mfold(rows, hwm)):
                    md_store[ci][li] = md
        out_records: List[List[Dict]] = []
        for ci, chunk in enumerate(pack.chunks):
            recs = []
            row_meta = pack.meta[ci]
            row_md = md_store[ci]
            for li, lb in enumerate(chunk.labels):
                sk, ent = row_meta[li]
                md = row_md[li]
                if md is not None:
                    # label fields from the skeleton template (dict
                    # insertion order == DesignPoint.label_fields)
                    rec = dict(sk.base_fields)
                    rec["logic"] = lb.logic
                    rec["hbm"] = lb.hbm
                    rec["net"] = lb.net
                    rec["scale"] = lb.scale
                    rec.update(md)
                    rec["key"] = (f"{sk.key_pre}|{lb.logic}|{lb.hbm}|"
                                  f"{lb.net}|{ent[3]}|{sk.key_suf}")
                else:
                    dp = self._design_point(lb, sk, ent[0])
                    rec = sk.scn.record(dp, rows_by_slot[(ci, li)])
                    rec["key"] = dp.key()
                recs.append(rec)
            out_records.append(recs)
        return out_records

    # -- compile-ahead (producer side) -------------------------------------
    def _prefetch(self, pack: _Pack) -> None:
        """Hand the pack's compiled-fn (key, padded shape) pairs to the
        AOT compile service so the executables are warm (or at least in
        flight) by the time the device stage reaches this pack.  Runs on
        the producer side; a miss just means the device stage compiles
        inline."""
        if not self.compile_ahead:
            return
        from repro.core import compileahead
        svc = compileahead.service()
        n_metrics = len(pathfinder.METRICS)

        def warm(key, build, args):
            if svc.warm(key, build, args):
                with self._pin_lock:
                    self._aot_pins[key] += 1

        def hw_aval(target, n_dev):
            if n_dev > 1:
                return jax.ShapeDtypeStruct(
                    (n_dev, target // n_dev, pathfinder.HW_DIM),
                    jnp.float32)
            return jax.ShapeDtypeStruct((target, pathfinder.HW_DIM),
                                        jnp.float32)

        if self._frontier_capacity is not None:
            capacity = self._frontier_capacity
            for g in pack.groups.values():
                n = len(g.ridx)
                if not n or g.skel.fold is None:
                    continue
                _, target = self._pad_plan(n)
                state = pathfinder.frontier_init(
                    capacity, len(g.skel.scn.objectives),
                    g.skel.ppd * n_metrics)
                st_avals = jax.tree_util.tree_map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                    state)
                warm(("frontier", g.keys, g.skel.fold_key, capacity),
                     self._frontier_build(g, capacity),
                     (jax.ShapeDtypeStruct((target, pathfinder.HW_DIM),
                                           jnp.float32),
                      jax.ShapeDtypeStruct((target,), jnp.int32),
                      st_avals))
            return
        if self.bucketing:
            for bucket, items in self._bucket_plan(pack).values():
                n = sum(ni for _, _, _, ni in items)
                n_dev, target = self._pad_plan(n)
                lead = (n_dev, target // n_dev) if n_dev > 1 else (target,)
                packs_avals = tuple(
                    jax.ShapeDtypeStruct(
                        lead + (bucket.class_sizes[c],) + tuple(shape),
                        np.dtype(dt))
                    for c, (dt, shape) in enumerate(bucket.classes))
                warm(("cabucket", bucket.id, n_dev),
                     compileahead.bucket_builder(bucket, n_dev),
                     (packs_avals, hw_aval(target, n_dev)))
        else:
            for g in pack.groups.values():
                n = len(g.ridx)
                if not n:
                    continue
                n_dev, target = self._pad_plan(n)
                warm(("design", g.keys, n_dev), self._eval_build(g, n_dev),
                     (hw_aval(target, n_dev),))

    # -- the pipeline -----------------------------------------------------
    def _pack_slices(self, chunks: Sequence) -> List[Sequence]:
        per = max(self.superbatch // max(self.spec.chunk_size, 1), 1)
        return [chunks[i:i + per] for i in range(0, len(chunks), per)]

    def _pack_ahead(self, chunks: Sequence) -> _Pack:
        """The producer stage for one superbatch: pack it and hand its
        compiled-fn shapes to the AOT service."""
        with jax.profiler.TraceAnnotation("repro.pipeline.pack"):
            pack = self.pack(chunks)
            self._prefetch(pack)
        return pack

    def _write(self, pack: _Pack, commit: Callable) -> int:
        """The writer stage for one superbatch: block on its device
        results, fold records and commit them in chunk order.  Returns the
        points committed."""
        with jax.profiler.TraceAnnotation("repro.pipeline.finalize"):
            n = 0
            for chunk, recs in zip(pack.chunks, self.finalize(pack)):
                n += len(recs)
                commit(chunk, recs)
            return n

    def run(self, chunks: Sequence, commit: Callable,
            verbose: bool = False) -> int:
        """Evaluate ``chunks``, invoking ``commit(chunk, records)`` in
        chunk order.  Returns evaluated points.

        Threaded mode runs producer/device/writer on separate threads;
        inline mode (small hosts) gets the same double buffering from JAX
        async dispatch alone: pack N+1 is resolved and dispatched before
        pack N's results are pulled, so the device is never idle while
        records fold and commit.
        """
        if not chunks:
            return 0
        slices = self._pack_slices(chunks)
        if not self.threads:
            n_points = 0
            prev: Optional[_Pack] = None
            buf: "collections.deque" = collections.deque()
            si = 0
            try:
                while si < len(slices) or buf:
                    # pack (and AOT-submit) up to compile_ahead
                    # superbatches past the one about to dispatch, so
                    # their compiles overlap this pack's device work
                    while si < len(slices) \
                            and len(buf) <= self.compile_ahead:
                        buf.append(self._pack_ahead(slices[si]))
                        si += 1
                    pack = buf.popleft()
                    with jax.profiler.TraceAnnotation(
                            "repro.pipeline.dispatch"):
                        self.dispatch(pack)  # async: pack N on device ...
                    if prev is not None:
                        # ... while N-1 commits
                        n_points += self._write(prev, commit)
                    prev = pack
                if prev is not None:
                    n_points += self._write(prev, commit)
            finally:
                self._release_all_pins()
            return n_points
        pack_q: "queue.Queue" = queue.Queue(maxsize=QUEUE_DEPTH)
        write_q: "queue.Queue" = queue.Queue(maxsize=QUEUE_DEPTH)
        errors: List[BaseException] = []
        n_points = [0]

        def produce():
            # the deque keeps compile_ahead packed superbatches in hand
            # beyond the bounded queue: each is AOT-submitted at pack
            # time, so its compiles run while earlier packs dispatch
            buf: "collections.deque" = collections.deque()
            try:
                for sl in slices:
                    if errors:
                        break
                    buf.append(self._pack_ahead(sl))
                    while len(buf) > self.compile_ahead:
                        pack_q.put(buf.popleft())
                while buf and not errors:
                    pack_q.put(buf.popleft())
            except BaseException as e:      # noqa: BLE001 — re-raised below
                errors.append(e)
            finally:
                pack_q.put(None)

        def write():
            # blocks on pack N-1's device results, folds records and
            # commits JSONL while the main thread keeps dispatching; on an
            # error it keeps draining so the bounded put()s never deadlock
            while True:
                pack = write_q.get()
                if pack is None:
                    return
                if errors:
                    continue
                try:
                    n_points[0] += self._write(pack, commit)
                except BaseException as e:  # noqa: BLE001 — re-raised below
                    errors.append(e)

        producer = threading.Thread(target=produce, daemon=True,
                                    name="sweep-producer")
        writer = threading.Thread(target=write, daemon=True,
                                  name="sweep-writer")
        producer.start()
        writer.start()
        try:
            while True:
                pack = _wait_pack(pack_q)
                if pack is None:
                    break
                if errors:
                    continue        # drain so the producer's put()s finish
                try:
                    # async dispatch: chunk N hits the device while N+1
                    # packs (producer) and N-1 folds/commits (writer); the
                    # bounded write queue is the in-flight backpressure
                    with jax.profiler.TraceAnnotation(
                            "repro.pipeline.dispatch"):
                        self.dispatch(pack)
                    write_q.put(pack)
                except BaseException as e:   # noqa: BLE001
                    errors.append(e)
        except BaseException as e:           # noqa: BLE001 (interrupts)
            errors.append(e)
        finally:
            write_q.put(None)
            writer.join()
            _join_producer(producer, pack_q)
            self._release_all_pins()
        if errors:
            raise errors[0]
        return n_points[0]

    # -- frontier-only mode ----------------------------------------------
    def run_frontier(self, chunks: Sequence,
                     capacity: int = pathfinder.FRONTIER_CAPACITY,
                     state=None, on_commit: Optional[Callable] = None,
                     all_chunks: Optional[Sequence] = None,
                     ) -> Tuple[List[Dict], int, int]:
        """Device-resident streaming-frontier sweep over ``chunks``.

        Returns ``(frontier records, n_overflowed, n_points_evaluated)``.
        The prediction cache is bypassed (rows stay on device; publishing
        them would mean materializing every row on host — the exact cost
        this mode exists to avoid) and per-point results are never
        collected: only the surviving frontier's records are rebuilt, from
        the carried state's payload rows.

        ``state`` seeds the carried frontier state (host arrays from a
        prior run's checkpoint); ``on_commit(chunk_indices, host_state)``
        fires after each merged superbatch with the chunk indices it
        folded in and the state materialized to host — the checkpoint
        hook.  ``all_chunks`` is the full enumeration when ``chunks`` is
        only the pending subset: carried payload rows reference global
        point indices, so record rebuild needs every chunk, merged or not.
        """
        all_chunks = list(all_chunks) if all_chunks is not None \
            else list(chunks)
        if not all_chunks:
            return [], 0, 0
        probe = all_chunks[0].labels[0]
        # the probe's hardware row goes through AGE with the first pack's
        head = self._pack_slices(chunks)[0] if len(chunks) else ()
        self._prime_rows([probe] + [lb for c in head for lb in c.labels])
        sk0 = self._skeleton(probe)
        if sk0.fold is None:
            raise ValueError(
                f"scenario {sk0.scn.name!r} defines no frontier_fold; "
                f"--frontier-only needs a device-side objective fold")
        n_obj = len(sk0.scn.objectives)
        payload_dim = sk0.ppd * len(pathfinder.METRICS)
        if state is None:
            state = pathfinder.frontier_init(capacity, n_obj, payload_dim)
        else:
            state = tuple(jnp.asarray(x) for x in state)

        cache, self.cache = self.cache, None    # frontier bypasses caching
        self._frontier_capacity = capacity      # _prefetch warms step fns
        n_points = 0
        try:
            slices = self._pack_slices(chunks)

            def merge_pack(pack: _Pack, state) -> Tuple[object, int]:
                n_merged = 0
                with jax.profiler.TraceAnnotation("repro.pipeline.dispatch"):
                    for g in pack.groups.values():
                        n = len(g.ridx)
                        if not n:
                            continue
                        hw, _ = self._padded(g)
                        idx = np.full(hw.shape[0], -1, dtype=np.int32)
                        idx[:n] = g.gidx
                        fn = self._compiled_frontier(g, capacity)
                        # async dispatch: the merge runs on device while
                        # the next pack resolves on host
                        state = fn(jnp.asarray(hw), jnp.asarray(idx), state)
                        self._release_pins(
                            ("frontier", g.keys, g.skel.fold_key, capacity))
                        n_merged += n
                return state, n_merged

            def commit_pack(pack: _Pack, state):
                with jax.profiler.TraceAnnotation("repro.pipeline.finalize"):
                    if on_commit is not None:
                        host = tuple(np.asarray(x) for x in state)
                        on_commit([c.index for c in pack.chunks], host)

            if not self.threads:
                buf: "collections.deque" = collections.deque()
                si = 0
                while si < len(slices) or buf:
                    while si < len(slices) \
                            and len(buf) <= self.compile_ahead:
                        buf.append(self._pack_ahead(slices[si]))
                        si += 1
                    pack = buf.popleft()
                    state, n = merge_pack(pack, state)
                    n_points += n
                    commit_pack(pack, state)
            else:
                pack_q: "queue.Queue" = queue.Queue(maxsize=QUEUE_DEPTH)
                errors: List[BaseException] = []

                def produce():
                    buf: "collections.deque" = collections.deque()
                    try:
                        for sl in slices:
                            if errors:
                                break
                            buf.append(self._pack_ahead(sl))
                            while len(buf) > self.compile_ahead:
                                pack_q.put(buf.popleft())
                        while buf and not errors:
                            pack_q.put(buf.popleft())
                    except BaseException as e:  # noqa: BLE001
                        errors.append(e)
                    finally:
                        pack_q.put(None)

                producer = threading.Thread(target=produce, daemon=True,
                                            name="sweep-producer")
                producer.start()
                try:
                    while True:
                        pack = _wait_pack(pack_q)
                        if pack is None:
                            break
                        if errors:
                            continue    # drain so the producer finishes
                        try:
                            state, n = merge_pack(pack, state)
                            n_points += n
                            commit_pack(pack, state)
                        except BaseException as e:  # noqa: BLE001
                            errors.append(e)
                finally:
                    _join_producer(producer, pack_q)
                if errors:
                    raise errors[0]
        finally:
            self.cache = cache
            self._frontier_capacity = None
            self._release_all_pins()

        records, n_over = self.frontier_records(state, all_chunks)
        return records, n_over, n_points

    def frontier_records(self, state,
                         all_chunks: Sequence) -> Tuple[List[Dict], int]:
        """Rebuild the surviving frontier's result records from a carried
        frontier state's payload rows: ``(records, n_overflowed)``.

        The state may come straight off `run_frontier`, a checkpoint, or a
        cross-worker `pathfinder.frontier_merge_states` merge — payload
        rows reference global point indices, so ``all_chunks`` must be the
        FULL enumeration.  Records are re-filtered host-side in float64
        (the device merge works in f32, so razor-edge ties could otherwise
        differ from the full-materialization frontier).
        """
        from repro.core import sweeprunner
        all_chunks = list(all_chunks)
        vals, payload, idx, n_over = pathfinder.frontier_unpack(
            tuple(np.asarray(x) for x in state))
        by_index = {c.index: c for c in all_chunks}
        order = np.argsort(idx)                 # enumeration order
        labels = []
        for i in order:
            gi = int(idx[i])
            chunk = by_index[gi // self.spec.chunk_size]
            labels.append(chunk.labels[gi % self.spec.chunk_size])
        self._prime_rows(labels)
        records: List[Dict] = []
        sk = None
        for i, lb in zip(order, labels):
            sk = self._skeleton(lb)
            hw = self._hw_entry(lb)[0]
            dp = self._design_point(lb, sk, hw)
            rows = payload[i].astype(np.float64).reshape(
                sk.ppd, len(pathfinder.METRICS))
            rec = sk.scn.record(dp, rows)
            rec["key"] = dp.key()
            records.append(rec)
        if not records:
            return [], n_over
        records = sweeprunner.pareto_records(
            records, tuple(sk.scn.objectives))
        return records, n_over
