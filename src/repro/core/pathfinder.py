"""Batched pathfinding engine — vectorized design-space sweeps over CrossFlow.

The paper's headline contribution is *automated* exploration of the
technology x hardware x software stack (§7, §9), which only pays off when the
evaluator can score thousands of candidate points cheaply (cf. DFModel,
COSMIC).  The per-point path (`simulate.predict`) walks the compute graph in
eager `jnp`, so a sweep costs O(points x graph-size) Python dispatches.

This module exploits the observation that for a fixed *skeleton* —
(compute graph, parallelism strategy, system graph, PPE config) — the whole
CrossFlow pipeline (AGE -> roofline -> placement -> event-driven sim) is pure
traceable `jax.numpy` code in the MicroArch's numeric leaves.  So:

  * `BatchedEvaluator` stacks MicroArch candidates into a struct-of-arrays
    hardware matrix and scores all of them with ONE `jax.jit(jax.vmap(...))`
    call per skeleton (compiled functions are cached per skeleton);
  * `evaluate_budgets` does the same over SOE budget vectors, batching
    through the differentiable AGE (`age.generate(discrete=False)`);
  * an LRU `PredictionCache` keyed on (graph fingerprint, strategy, system,
    ppe, hardware point) makes repeated points across SOE multi-starts and
    planner calls free;
  * `BatchedEvaluator.evaluate_matrix` is the matrix-native fast path: an
    (N, HW_DIM) struct-of-arrays hardware matrix is scored without building
    per-point MicroArch objects, optionally `jax.pmap`-sharded row-wise
    across every local device (the 10^4-10^6-point sweep regime of
    repro.core.sweeprunner);
  * `sweep` cross-products arches x shape cells x mesh shapes x techlib
    nodes and returns every point plus the Pareto frontier.

`benchmarks/sweep_scale.py` measures the resulting throughput (points/sec)
against the per-point loop on the Fig. 9 tech-scaling sweep;
`benchmarks/sweep_shard.py` measures the sharded matrix path against the
single-stream evaluator.  For chunked, checkpointed, resumable sweeps (and
the serving scenario) see `repro.core.sweeprunner` / `repro.core.scenarios`.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import os
import threading
import time
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import age as age_lib
from repro.core import simulate
from repro.core import techlib as techlib_lib
from repro.core.age import Budgets, MicroArch
from repro.core.graph import ComputeGraph
from repro.core.parallelism import Strategy
from repro.core.placement import SystemGraph
from repro.core.roofline import PPEConfig
from repro.core.techlib import TechConfig

# ---------------------------------------------------------------------------
# Struct-of-arrays hardware points
# ---------------------------------------------------------------------------

# The MicroArch leaves the performance model actually consumes.  Everything
# else on MicroArch (n_mcu, link counts, on-chip latencies) is either unused
# by `simulate.predict` or static per technology entry and taken from the
# batch's template arch.
HW_FIELDS: Tuple[str, ...] = (
    "compute_throughput",
    "mem_capacity_l0", "mem_capacity_l1", "mem_capacity_l2",
    "mem_bw_l0", "mem_bw_l1", "mem_bw_l2",
    "dram_capacity", "dram_bw",
    "net_intra_bw", "net_inter_bw",
    "net_intra_latency", "net_inter_latency",
    # energy/cost coefficients for the objective layer
    # (repro.core.objectives).  Appended AFTER the performance leaves so
    # `unpack_hw`'s positional reads — and every persisted payload that
    # slices the first 13 columns — stay valid.
    "energy_per_flop", "dram_energy_per_byte", "net_energy_per_byte",
    "static_power_w", "device_cost_usd",
)
HW_DIM = len(HW_FIELDS)

# columns of the energy/cost coefficient block (ctx keys for objectives)
HW_COEFF_FIELDS: Tuple[str, ...] = HW_FIELDS[13:]


def hw_coeffs(arch: MicroArch) -> Dict[str, object]:
    """Energy/cost coefficients of one hardware point, keyed per HW_FIELDS.

    The single definition shared by `pack_hw` (host floats into the
    struct-of-arrays matrix) and cooptimize's traced refine ctx (jnp
    tracers when the DVFS knobs ride through `arch.tech`): per-flop and
    per-byte dynamic energies, aggregate static power, and device capex
    from the per-tech cost table.  Plain arithmetic — traceable.
    """
    t = arch.tech
    return {
        "energy_per_flop": t.compute.energy_per_flop,
        "dram_energy_per_byte": t.dram.dynamic_energy_per_bit * 8.0,
        "net_energy_per_byte": t.net_inter.nominal_energy_per_bit * 8.0,
        "static_power_w": techlib_lib.static_power_w(
            t, arch.dram_capacity, arch.compute_throughput),
        "device_cost_usd": techlib_lib.device_cost_usd(
            t, arch.dram_capacity),
    }


def hw_ctx(arch: MicroArch) -> Dict[str, object]:
    """Objective-fold hardware ctx for a (possibly traced) MicroArch.

    The refine-path analogue of reading `pack_hw` columns: the hardware
    keys of the `repro.core.objectives` ctx contract, live-valued so
    cooptimize differentiates energy/cost through the DVFS knobs.
    """
    ctx = hw_coeffs(arch)
    ctx["compute_throughput"] = arch.compute_throughput
    ctx["dram_bw"] = arch.dram_bw
    ctx["net_inter_bw"] = arch.net_inter_bw
    ctx["dram_capacity"] = arch.dram_capacity
    return ctx


def pack_hw(arch: MicroArch) -> np.ndarray:
    """Flatten the batchable MicroArch leaves into a (HW_DIM,) f32 vector.

    Host-side (NumPy): packing thousands of points must not pay per-leaf
    JAX dispatch; the batch crosses into JAX once, already stacked.
    """
    coeffs = hw_coeffs(arch)
    return np.asarray([
        float(arch.compute_throughput),
        float(arch.mem_capacity[0]),
        float(arch.mem_capacity[1]),
        float(arch.mem_capacity[2]),
        float(arch.mem_bw[0]),
        float(arch.mem_bw[1]),
        float(arch.mem_bw[2]),
        float(arch.dram_capacity),
        float(arch.dram_bw),
        float(arch.net_intra_bw),
        float(arch.net_inter_bw),
        float(arch.net_intra_latency),
        float(arch.net_inter_latency),
    ] + [float(coeffs[k]) for k in HW_COEFF_FIELDS], dtype=np.float32)


def unpack_hw(template: MicroArch, v) -> MicroArch:
    """Rebuild a MicroArch from a (HW_DIM,) vector; static leaves (tech,
    latencies of on-chip levels, link counts) come from `template`."""
    return dataclasses.replace(
        template,
        compute_throughput=v[0],
        mem_capacity=(v[1], v[2], v[3]),
        mem_bw=(v[4], v[5], v[6]),
        dram_capacity=v[7],
        dram_bw=v[8],
        net_intra_bw=v[9],
        net_inter_bw=v[10],
        net_intra_latency=v[11],
        net_inter_latency=v[12],
    )


def _hw_key(arch: MicroArch) -> bytes:
    """Hashable identity of one hardware point (cache key component)."""
    return pack_hw(arch).tobytes()


# The five timing components one prediction returns (TimeBreakdown order).
METRICS: Tuple[str, ...] = ("total_s", "compute_s", "comm_s",
                            "exposed_comm_s", "pipeline_bubble_s")


def _breakdown_row(bd: simulate.TimeBreakdown) -> np.ndarray:
    return np.asarray([float(bd.total_s), float(bd.compute_s),
                       float(bd.comm_s), float(bd.exposed_comm_s),
                       float(bd.pipeline_bubble_s)], dtype=np.float64)


# ---------------------------------------------------------------------------
# LRU prediction cache
# ---------------------------------------------------------------------------


class PredictionCache:
    """LRU cache of prediction rows keyed on (skeleton, hardware point).

    Thread-safe: the sweep runner (repro.core.sweeprunner) shares one cache
    across worker threads, so all bookkeeping happens under a lock.
    """

    def __init__(self, maxsize: int = 65536):
        self.maxsize = maxsize
        self._data: "collections.OrderedDict" = collections.OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    def get(self, key) -> Optional[np.ndarray]:
        with self._lock:
            row = self._data.get(key)
            if row is None:
                self.misses += 1
                return None
            self._data.move_to_end(key)
            self.hits += 1
            return row

    def get_many(self, keys: Sequence) -> List[Optional[np.ndarray]]:
        """Batched lookup: one lock pass for a whole hardware matrix.

        The pipelined sweep executor probes thousands of keys per chunk;
        per-key `get` calls would take and release the lock (and bump the
        LRU bookkeeping) once per point.
        """
        out: List[Optional[np.ndarray]] = []
        with self._lock:
            for key in keys:
                row = self._data.get(key)
                if row is None:
                    self.misses += 1
                else:
                    self._data.move_to_end(key)
                    self.hits += 1
                out.append(row)
        return out

    def put(self, key, row: np.ndarray) -> None:
        with self._lock:
            self._data[key] = row
            self._data.move_to_end(key)
            while len(self._data) > self.maxsize:
                self._data.popitem(last=False)

    def put_many(self, pairs: Sequence[Tuple]) -> None:
        """Batched insert (one lock pass); same LRU semantics as `put`."""
        with self._lock:
            for key, row in pairs:
                self._data[key] = row
                self._data.move_to_end(key)
            while len(self._data) > self.maxsize:
                self._data.popitem(last=False)

    def clear(self) -> None:
        with self._lock:
            self._data.clear()
            self.hits = 0
            self.misses = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._data)

    @property
    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "size": len(self._data)}


_PREDICTION_CACHE = PredictionCache()

# sentinel meaning "use whatever prediction_cache() returns at CALL time".
# A plain `cache=_PREDICTION_CACHE` default would freeze the singleton at
# import time, so replacing the module-level cache (tests, embedding apps)
# would silently leave default-arg callers on the dead object.  `None`
# still means "no cache at all".
DEFAULT_CACHE = object()


def resolve_cache(cache) -> Optional[PredictionCache]:
    """Map the `DEFAULT_CACHE` sentinel to the live singleton (late
    binding); pass real caches and None (= caching disabled) through."""
    return prediction_cache() if cache is DEFAULT_CACHE else cache


def prediction_cache() -> PredictionCache:
    return _PREDICTION_CACHE


def set_prediction_cache(cache: PredictionCache) -> PredictionCache:
    """Replace the process-wide prediction cache (takes effect for every
    default-arg caller immediately — see `DEFAULT_CACHE`)."""
    global _PREDICTION_CACHE
    _PREDICTION_CACHE = cache
    return cache


def cache_stats() -> Dict[str, int]:
    return _PREDICTION_CACHE.stats


def clear_prediction_cache() -> None:
    _PREDICTION_CACHE.clear()


# ---------------------------------------------------------------------------
# Batched evaluator (one skeleton, many hardware points)
# ---------------------------------------------------------------------------

# LRU of jitted per-skeleton evaluation functions.  Each entry captures a
# compiled XLA executable plus the closed-over graph, so unlike the
# lightweight PredictionCache this must stay small and evict.  Guarded by a
# lock so thread-parallel sweep workers get one wrapped function per
# skeleton (jit/pmap wrapping is lazy, so holding the lock is cheap; the
# actual XLA compile happens at first call, outside the lock).
_COMPILED: "collections.OrderedDict[tuple, Callable]" = \
    collections.OrderedDict()
_COMPILED_MAXSIZE = int(os.environ.get("REPRO_COMPILED_MAXSIZE", "128"))
_COMPILED_LOCK = threading.Lock()
# Pin counts per store key.  A pinned entry is never evicted by the LRU
# sweep — the AOT compile service pins a key from the moment it is queued
# until its first dispatch, so an executable compiled off-path can't be
# popped (and silently recompiled on-path) between build and use.  The
# store may transiently exceed maxsize while pins are held.
_COMPILED_PINS: Dict[tuple, int] = {}
# hit/miss counts over EVERY compiled-function store that goes through
# `_compiled_get_or_create` (skeleton evaluators, budget fns, the pipelined
# design/frontier fns).  A miss = one wrapped fn built, i.e. one XLA
# compile per input shape at first call; the sweep runner surfaces the
# per-run delta so compile churn is visible from the CLI summary line.
# `compile_seconds` accumulates wall time spent inside XLA lower+compile
# (wherever it runs: AOT service threads or the dispatch path);
# `stall_seconds` counts only the time a *dispatching* caller was blocked
# waiting for a compile — the number compile-ahead exists to drive to zero.
_COMPILE_STATS = {"hits": 0, "misses": 0,
                  "compile_seconds": 0.0, "stall_seconds": 0.0}


def compile_cache_stats() -> Dict[str, float]:
    """Process-wide compiled-evaluator cache counters.

    ``hits``/``misses`` count store lookups (ints); ``compile_seconds`` /
    ``stall_seconds`` are cumulative wall-clock floats (see comments on
    `_COMPILE_STATS`).
    """
    with _COMPILED_LOCK:
        return dict(_COMPILE_STATS)


def set_compiled_maxsize(n: int) -> int:
    """Set the compiled-function LRU capacity; returns the previous value.

    Also configurable at process start via env ``REPRO_COMPILED_MAXSIZE``.
    Pinned (AOT-queued / in-flight) entries are exempt from eviction, so
    the store may transiently hold more than ``n`` entries.
    """
    global _COMPILED_MAXSIZE
    if n <= 0:
        raise ValueError(f"compiled maxsize must be positive, got {n}")
    with _COMPILED_LOCK:
        prev, _COMPILED_MAXSIZE = _COMPILED_MAXSIZE, n
        _evict_locked(_COMPILED)
    return prev


def compiled_maxsize() -> int:
    return _COMPILED_MAXSIZE


def pin_compiled(key: tuple) -> None:
    """Protect `key` from LRU eviction until the matching `unpin_compiled`.

    Reentrant (a pin count is kept).  Pinning a key that is not in the
    store yet is allowed — the AOT service pins at submit time, before the
    wrapped function has been built.
    """
    with _COMPILED_LOCK:
        _COMPILED_PINS[key] = _COMPILED_PINS.get(key, 0) + 1


def unpin_compiled(key: tuple) -> None:
    with _COMPILED_LOCK:
        n = _COMPILED_PINS.get(key, 0) - 1
        if n > 0:
            _COMPILED_PINS[key] = n
        else:
            _COMPILED_PINS.pop(key, None)
        _evict_locked(_COMPILED)


def _evict_locked(store: "collections.OrderedDict") -> None:
    # Caller holds _COMPILED_LOCK.  Evict oldest unpinned entries until the
    # store fits; pinned entries are skipped (and keep their LRU position).
    excess = len(store) - _COMPILED_MAXSIZE
    if excess <= 0:
        return
    for key in list(store):
        if excess <= 0:
            break
        if _COMPILED_PINS.get(key):
            continue
        del store[key]
        excess -= 1


def _add_compile_seconds(dt: float, stalled: bool) -> None:
    with _COMPILED_LOCK:
        _COMPILE_STATS["compile_seconds"] += dt
        if stalled:
            _COMPILE_STATS["stall_seconds"] += dt


def _add_stall_seconds(dt: float) -> None:
    with _COMPILED_LOCK:
        _COMPILE_STATS["stall_seconds"] += dt


def _compiled_get_or_create(store: "collections.OrderedDict", key: tuple,
                            build: Callable[[], Callable]) -> Callable:
    with _COMPILED_LOCK:
        fn = store.get(key)
        if fn is not None:
            store.move_to_end(key)
            _COMPILE_STATS["hits"] += 1
            return fn
        fn = build()
        store[key] = fn
        _COMPILE_STATS["misses"] += 1
        _evict_locked(store)
        return fn


class CompiledEntry:
    """A `_COMPILED` store value that can hold ahead-of-time executables.

    Wraps a lazy jit/pmap transform (``wrapper``) plus a table of
    `.lower().compile()`-ed executables keyed by input shape signature.
    Dispatch prefers a finished AOT executable; if a compile for the
    needed signature is in flight (AOT service), the caller blocks on it
    (counted as stall_seconds) instead of compiling a duplicate; on a
    plain miss it compiles inline (counted as compile+stall).  One compile
    per (key, signature) per process: `compile_for` dedupes via
    per-signature events.  A failed compile is recorded and raised again
    by every dispatch that needs it — never retried lazily.
    """

    def __init__(self, key: tuple, wrapper: Callable):
        self.key = key
        self.wrapper = wrapper
        self.aot: Dict[tuple, Callable] = {}
        self.failed: Dict[tuple, BaseException] = {}
        self._inflight: Dict[tuple, threading.Event] = {}
        self._lock = threading.Lock()

    @staticmethod
    def signature(args: tuple) -> tuple:
        leaves = jax.tree_util.tree_leaves(args)
        return tuple((tuple(l.shape), str(np.asarray(l).dtype) if not
                      hasattr(l, "dtype") else str(l.dtype)) for l in leaves)

    def _avals(self, args: tuple):
        return jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), args)

    def compile_for(self, args: tuple, stalled: bool = False) -> None:
        """Ensure an executable exists for the shape signature of `args`.

        `args` may be concrete arrays or `jax.ShapeDtypeStruct`s.  Safe to
        call from any thread; concurrent calls for one signature collapse
        into a single compile (the rest wait).  Raises the compiler's
        error, here and in every later call for the same signature.
        """
        sig = self.signature(args)
        with self._lock:
            if sig in self.aot:
                return
            if sig in self.failed:
                raise self.failed[sig]
            ev = self._inflight.get(sig)
            if ev is None:
                ev = self._inflight[sig] = threading.Event()
                owner = True
            else:
                owner = False
        if not owner:
            t0 = time.perf_counter()
            ev.wait()
            if stalled:
                _add_stall_seconds(time.perf_counter() - t0)
            if sig in self.failed:
                raise self.failed[sig]
            return
        t0 = time.perf_counter()
        try:
            self.aot[sig] = self.wrapper.lower(*self._avals(args)).compile()
        except Exception as e:
            self.failed[sig] = e
            raise
        finally:
            _add_compile_seconds(time.perf_counter() - t0, stalled)
            with self._lock:
                self._inflight.pop(sig, None)
            ev.set()

    def __call__(self, *args):
        exe = self.aot.get(self.signature(args))
        if exe is None:
            self.compile_for(args, stalled=True)
            exe = self.aot[self.signature(args)]
        return exe(*args)


def compiled_entry(key: tuple,
                   build_wrapper: Callable[[], Callable]) -> CompiledEntry:
    """Get-or-create a `CompiledEntry` in the process-wide `_COMPILED` LRU.

    Like `_compiled_get_or_create` but the stored value is an AOT-capable
    entry (see `CompiledEntry`); hit/miss accounting is shared.
    """
    return _compiled_get_or_create(
        _COMPILED, key, lambda: CompiledEntry(key, build_wrapper()))


def clear_compiled_caches() -> None:
    """Drop every cached jitted/pmapped evaluation function (benchmarks use
    this to measure cold-compile paths; also frees the closed-over graphs).
    Pins are dropped too, and the compile-ahead bucket registry is reset so
    canonical executables are rebuilt from scratch."""
    with _COMPILED_LOCK:
        _COMPILED.clear()
        _BUDGET_COMPILED.clear()
        _COMPILED_PINS.clear()
    from . import compileahead
    compileahead._clear_registries()


def _skeleton_key(graph_fp: str, strategy: Strategy,
                  system: SystemGraph, ppe: PPEConfig, overlap: bool,
                  n_microbatches: Optional[int], pod_bw: Optional[float],
                  systolic_dims: tuple) -> tuple:
    return (graph_fp, strategy, system, ppe, overlap, n_microbatches,
            pod_bw, tuple(systolic_dims))


class BatchedEvaluator:
    """Scores many MicroArch candidates on one (graph, strategy, system).

    The scalar prediction is traced once per skeleton, `jax.vmap`-ed over the
    hardware matrix and `jax.jit`-ed; compiled functions are cached
    process-wide so repeated evaluators on the same skeleton are free.
    """

    def __init__(self, graph: ComputeGraph, strategy: Strategy,
                 system: Optional[SystemGraph] = None,
                 ppe: PPEConfig = PPEConfig(), overlap: bool = True,
                 n_microbatches: Optional[int] = None,
                 pod_bw: Optional[float] = None,
                 cache: Optional[PredictionCache] = DEFAULT_CACHE,
                 bucketed: Optional[bool] = None):
        self.graph = graph
        self.strategy = strategy
        self.system = system or simulate.default_system(strategy)
        self.ppe = ppe
        self.overlap = overlap
        self.n_microbatches = n_microbatches
        self.pod_bw = pod_bw
        self.cache = resolve_cache(cache)
        self.bucketed = bucketed
        self._graph_fp = graph.fingerprint()

    # -- compiled path ----------------------------------------------------
    def _skeleton(self, template: MicroArch) -> tuple:
        return _skeleton_key(self._graph_fp, self.strategy, self.system,
                             self.ppe, self.overlap, self.n_microbatches,
                             self.pod_bw,
                             template.tech.compute.systolic_dims)

    def _scalar_fn(self, template: MicroArch) -> Callable:
        def scalar(v):
            arch = unpack_hw(template, v)
            bd = simulate.predict(
                arch, self.graph, self.strategy, system=self.system,
                cfg=self.ppe, overlap=self.overlap,
                n_microbatches=self.n_microbatches, pod_bw=self.pod_bw)
            return jnp.stack([
                jnp.asarray(bd.total_s, dtype=jnp.float32),
                jnp.asarray(bd.compute_s, dtype=jnp.float32),
                jnp.asarray(bd.comm_s, dtype=jnp.float32),
                jnp.asarray(bd.exposed_comm_s, dtype=jnp.float32),
                jnp.asarray(bd.pipeline_bubble_s, dtype=jnp.float32),
            ])
        return scalar

    def _use_bucketed(self) -> bool:
        from repro.core import compileahead
        return compileahead.resolve_bucketed(self.bucketed)

    def _compiled(self, template: MicroArch,
                  bucketed: Optional[bool] = None) -> Callable:
        key = self._skeleton(template)
        use = self._use_bucketed() if bucketed is None else bucketed
        if use:
            from repro.core import compileahead
            return compileahead.design_batch_fn(
                ("skel", key), lambda: self._scalar_fn(template),
                (jax.ShapeDtypeStruct((HW_DIM,), jnp.float32),), n_dev=1)
        return _compiled_get_or_create(
            _COMPILED, key,
            lambda: jax.jit(jax.vmap(self._scalar_fn(template))))

    def _compiled_sharded(self, template: MicroArch, n_dev: int,
                          bucketed: Optional[bool] = None) -> Callable:
        key = self._skeleton(template) + ("pmap", n_dev)
        use = self._use_bucketed() if bucketed is None else bucketed
        if use:
            from repro.core import compileahead
            return compileahead.design_batch_fn(
                ("skel", self._skeleton(template)),
                lambda: self._scalar_fn(template),
                (jax.ShapeDtypeStruct((HW_DIM,), jnp.float32),), n_dev=n_dev)
        return _compiled_get_or_create(
            _COMPILED, key,
            lambda: jax.pmap(jax.vmap(self._scalar_fn(template))))

    # -- public API -------------------------------------------------------
    def evaluate(self, archs: Sequence[MicroArch],
                 min_batch_jit: int = 2,
                 shard_devices: bool = False,
                 shard_block: int = 0) -> np.ndarray:
        """Score MicroArch candidates -> (B, 5) rows ordered like METRICS.

        Cached points are returned for free; only misses are evaluated, in a
        single vmapped call (or eagerly when fewer than `min_batch_jit`
        misses remain — avoids paying XLA compile time for one-off points).
        With ``shard_devices`` the miss batch is split across all local JAX
        devices via `evaluate_matrix` (pmap over the hardware matrix);
        ``shard_block`` is forwarded as its padding block so sweeps with
        varying per-call miss counts reuse a few compiled shapes.
        """
        archs = list(archs)
        if not archs:
            return np.zeros((0, len(METRICS)), dtype=np.float64)
        sd0 = tuple(archs[0].tech.compute.systolic_dims)
        for a in archs:
            if tuple(a.tech.compute.systolic_dims) != sd0:
                raise ValueError("mixed systolic dims in one batch; group "
                                 "points with evaluate_points() instead")
        out = np.zeros((len(archs), len(METRICS)), dtype=np.float64)
        skel = self._skeleton(archs[0])
        vecs = [pack_hw(a) for a in archs]
        misses: List[int] = []
        keys: List[Optional[tuple]] = []
        for i, a in enumerate(archs):
            key = (skel, vecs[i].tobytes()) if self.cache is not None \
                else None
            keys.append(key)
            row = self.cache.get(key) if self.cache is not None else None
            if row is None:
                misses.append(i)
            else:
                out[i] = row
        if not misses:
            return out
        if shard_devices and len(misses) >= max(min_batch_jit,
                                                jax.local_device_count()):
            rows = self.evaluate_matrix(archs[0],
                                        np.stack([vecs[i] for i in misses]),
                                        block=shard_block)
        elif len(misses) >= min_batch_jit or self._use_bucketed():
            # With bucketing on, even tiny miss batches go through the
            # shared canonical executable: the compile is amortized across
            # every design in the bucket, and rows stay bit-identical to
            # the batched/pipelined paths (the eager fallback differs at
            # float32 rounding).
            fn = self._compiled(archs[0])
            hw = jnp.asarray(np.stack([vecs[i] for i in misses]))
            rows = np.asarray(fn(hw), dtype=np.float64)
        else:
            rows = np.stack([self._eager_row(archs[i]) for i in misses])
        for j, i in enumerate(misses):
            out[i] = rows[j]
            if self.cache is not None:
                self.cache.put(keys[i], rows[j])
        return out

    def evaluate_matrix(self, template: MicroArch, hw_matrix,
                        devices: Optional[int] = None,
                        block: int = 0) -> np.ndarray:
        """Score an (N, HW_DIM) struct-of-arrays hardware matrix directly.

        The matrix-native fast path for sweeps at the 10^4-10^6 point scale
        (repro.core.sweeprunner): no per-point MicroArch objects, no
        per-point cache keys — the batch enters JAX as one array.  With
        ``devices`` > 1 (default: every local JAX device) the matrix is
        sharded row-wise across devices with `jax.pmap`, which on CPU hosts
        means one XLA executable per device thread running concurrently.

        ``block`` > 0 pads N up to a multiple of ``block`` x devices so
        successive chunks of a sweep share one compiled shape (jit/pmap
        specialize per input shape; without padding every distinct chunk
        size would recompile).  Padding rows replicate the last point and
        are sliced off the result.
        """
        hw = np.asarray(hw_matrix, dtype=np.float32)
        n = hw.shape[0]
        if n == 0:
            return np.zeros((0, len(METRICS)), dtype=np.float64)
        if hw.ndim != 2 or hw.shape[1] != HW_DIM:
            raise ValueError(f"hw_matrix must be (N, {HW_DIM}), "
                             f"got {hw.shape}")
        n_dev = devices if devices is not None else jax.local_device_count()
        n_dev = max(min(n_dev, n), 1)
        quantum = n_dev * max(block, 1)
        target = -(-n // quantum) * quantum
        if target != n:
            hw = np.concatenate(
                [hw, np.repeat(hw[-1:], target - n, axis=0)])
        # template+matrix mode is ONE design over a huge hardware batch:
        # there is nothing for cross-design bucketing to amortize, and the
        # parameterized bucket executable pays per-row coefficient gathers
        # plus lost constant folding at warm runtime (~16x slower on 16k
        # rows) — always dispatch the legacy baked executable here
        if n_dev > 1:
            fn = self._compiled_sharded(template, n_dev, bucketed=False)
            rows = fn(jnp.asarray(hw.reshape(n_dev, target // n_dev,
                                             HW_DIM)))
            rows = np.asarray(rows, dtype=np.float64).reshape(
                target, len(METRICS))
        else:
            fn = self._compiled(template, bucketed=False)
            rows = np.asarray(fn(jnp.asarray(hw)), dtype=np.float64)
        return rows[:n]

    def _eager_row(self, arch: MicroArch) -> np.ndarray:
        bd = simulate.predict(arch, self.graph, self.strategy,
                              system=self.system, cfg=self.ppe,
                              overlap=self.overlap,
                              n_microbatches=self.n_microbatches,
                              pod_bw=self.pod_bw)
        return _breakdown_row(bd)


# ---------------------------------------------------------------------------
# Heterogeneous point sets (different graphs / strategies / systems)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class EvalPoint:
    """One (hardware, workload, strategy, system) candidate."""

    arch: MicroArch
    graph: ComputeGraph
    strategy: Strategy
    system: Optional[SystemGraph] = None
    pod_bw: Optional[float] = None


def _evaluate_points_impl(points: Sequence[EvalPoint],
                          ppe: PPEConfig = PPEConfig(),
                          cache: Optional[PredictionCache] = DEFAULT_CACHE,
                          min_batch_jit: int = 4,
                          shard_devices: bool = False,
                          shard_block: int = 0) -> np.ndarray:
    """Score a heterogeneous candidate list -> (N, 5) metric matrix.

    Points are grouped by skeleton (graph fingerprint, strategy, system,
    ppe); each group is one struct-of-arrays batch.  Hardware-only axes
    (techlib nodes, budget variants) therefore collapse into single vmapped
    calls, while structure-changing axes (strategy, mesh) form their own
    groups and still benefit from the LRU cache.  ``shard_devices`` fans
    each group's hardware matrix across local JAX devices (see
    `BatchedEvaluator.evaluate_matrix`).
    """
    out = np.zeros((len(points), len(METRICS)), dtype=np.float64)
    groups: Dict[tuple, List[int]] = {}
    evaluators: Dict[tuple, BatchedEvaluator] = {}
    for i, p in enumerate(points):
        ev = BatchedEvaluator(p.graph, p.strategy, system=p.system, ppe=ppe,
                              pod_bw=p.pod_bw, cache=cache)
        key = ev._skeleton(p.arch)
        groups.setdefault(key, []).append(i)
        evaluators.setdefault(key, ev)
    for key, idxs in groups.items():
        ev = evaluators[key]
        rows = ev.evaluate([points[i].arch for i in idxs],
                           min_batch_jit=min_batch_jit,
                           shard_devices=shard_devices,
                           shard_block=shard_block)
        for j, i in enumerate(idxs):
            out[i] = rows[j]
    return out


def evaluate(points: Optional[Sequence[EvalPoint]] = None, *,
             spec=None, labels=None,
             template: Optional[MicroArch] = None, matrix=None,
             graph: Optional[ComputeGraph] = None,
             strategy: Optional[Strategy] = None,
             system: Optional[SystemGraph] = None,
             pod_bw: Optional[float] = None,
             ppe: PPEConfig = PPEConfig(),
             cache: Optional[PredictionCache] = DEFAULT_CACHE,
             min_batch_jit: int = 4,
             shard_devices: bool = False,
             shard_block: int = 0,
             devices: Optional[int] = None) -> np.ndarray:
    """Score candidates — THE eval entry point, in one of three modes.

    Exactly one mode per call (mixing raises ``ValueError``):

    * **points mode** — ``evaluate(points=[EvalPoint, ...])``: a
      heterogeneous candidate list, grouped by skeleton so hardware-only
      axes collapse into single vmapped calls; returns an ``(N, 5)``
      float64 matrix ordered like `METRICS`.
    * **label mode** — ``evaluate(spec=SweepSpec, labels=[PointLabel,
      ...])``: resolves sweep labels through their scenario (PPE/profile
      come from the spec, not the ``ppe`` argument) and returns the
      scenario's *result records* (list of dicts), exactly what
      `SweepRunner` commits per chunk.
    * **matrix mode** — ``evaluate(template=MicroArch, matrix=(N,
      HW_DIM), graph=..., strategy=...)``: the matrix-native fast path;
      rows enter JAX as one array, optionally pmap-sharded row-wise
      across ``devices`` with ``shard_block`` padding.

    Supersedes the three historical entry points
    (`sweeprunner.eval_labels`, `evaluate_points`,
    `BatchedEvaluator.evaluate_matrix`), which remain as thin
    deprecation wrappers.
    """
    n_modes = sum((points is not None,
                   spec is not None or labels is not None,
                   template is not None or matrix is not None))
    if n_modes != 1:
        raise ValueError(
            "evaluate() takes exactly one of: points=..., "
            "(spec=..., labels=...), or (template=..., matrix=...)")
    if points is not None:
        return _evaluate_points_impl(points, ppe=ppe, cache=cache,
                                     min_batch_jit=min_batch_jit,
                                     shard_devices=shard_devices,
                                     shard_block=shard_block)
    if matrix is not None or template is not None:
        if template is None or matrix is None or graph is None \
                or strategy is None:
            raise ValueError("matrix mode needs template=, matrix=, "
                             "graph= and strategy=")
        ev = BatchedEvaluator(graph, strategy, system=system, ppe=ppe,
                              pod_bw=pod_bw, cache=cache)
        return ev.evaluate_matrix(template, matrix, devices=devices,
                                  block=shard_block)
    if spec is None or labels is None:
        raise ValueError("label mode needs both spec= and labels=")
    from repro.core import sweeprunner   # lazy: sweeprunner imports us
    return sweeprunner._eval_labels_impl(spec, labels, cache=cache,
                                         shard_devices=shard_devices)


def evaluate_points(points: Sequence[EvalPoint],
                    ppe: PPEConfig = PPEConfig(),
                    cache: Optional[PredictionCache] = DEFAULT_CACHE,
                    min_batch_jit: int = 4,
                    shard_devices: bool = False,
                    shard_block: int = 0) -> np.ndarray:
    """Deprecated alias — use ``evaluate(points=...)`` (one documented
    facade over the three historical eval entry points)."""
    import warnings
    warnings.warn("pathfinder.evaluate_points is deprecated; use "
                  "pathfinder.evaluate(points=...)",
                  DeprecationWarning, stacklevel=2)
    return _evaluate_points_impl(points, ppe=ppe, cache=cache,
                                 min_batch_jit=min_batch_jit,
                                 shard_devices=shard_devices,
                                 shard_block=shard_block)


# ---------------------------------------------------------------------------
# Budget-space batching (the SOE axis)
# ---------------------------------------------------------------------------


_BUDGET_COMPILED: "collections.OrderedDict[tuple, Callable]" = \
    collections.OrderedDict()


def evaluate_budgets(tech: TechConfig, graph: ComputeGraph,
                     strategy: Strategy, budget_vectors,
                     system: Optional[SystemGraph] = None,
                     template: Optional[Budgets] = None,
                     ppe: PPEConfig = PPEConfig(),
                     pod_bw: Optional[float] = None) -> jnp.ndarray:
    """Score a (B, DIM) stack of SOE budget vectors in one vmapped call.

    The budget-space analogue of `BatchedEvaluator.evaluate`: goes through
    the differentiable AGE (`discrete=False`), so the result is also
    differentiable w.r.t. the budget stack.  (`soe.optimize` builds its own
    vmapped value_and_grad over the same objective for the GD loop; use
    this for one-shot batched budget scans.)  The jitted function is
    memoized per (tech, graph, strategy, system, ppe, template) skeleton.
    """
    like = template or Budgets.default()
    key = (tech, graph.fingerprint(), strategy, system, ppe, pod_bw,
           like.node_area_mm2, like.proc_chip_area_mm2, like.power_w)

    def build():
        def f(w):
            budgets = Budgets.from_vector(w, like)
            arch = age_lib.generate(tech, budgets, discrete=False)
            bd = simulate.predict(arch, graph, strategy, system=system,
                                  cfg=ppe, pod_bw=pod_bw)
            return bd.total_s

        return jax.jit(jax.vmap(f))

    fn = _compiled_get_or_create(_BUDGET_COMPILED, key, build)
    return fn(jnp.asarray(budget_vectors, dtype=jnp.float32))


# ---------------------------------------------------------------------------
# Pareto frontier
# ---------------------------------------------------------------------------


def pareto_front(points: Sequence, objectives: Sequence[Callable]) -> List:
    """Non-dominated subset minimizing every objective (callables on points).

    O(n^2); returns points in input order.  A point is kept iff no other
    point is <= on all objectives and < on at least one.  Tie semantics:
    points exactly equal on ALL objectives do not dominate each other, so
    every copy of a non-dominated point survives, independent of input
    order (same contract as `sweeprunner.pareto_records`; regression tests
    pin the two to each other).  Points with any non-finite objective are
    excluded — NaN compares false against everything, so such a point can
    never be dominated and would otherwise pollute the frontier.
    """
    vals = [tuple(float(obj(p)) for obj in objectives) for p in points]
    finite = [all(np.isfinite(v) for v in vi) for vi in vals]
    keep = []
    for i, vi in enumerate(vals):
        if not finite[i]:
            continue
        dominated = False
        for j, vj in enumerate(vals):
            if j == i or not finite[j]:
                continue
            if all(a <= b for a, b in zip(vj, vi)) \
                    and any(a < b for a, b in zip(vj, vi)):
                dominated = True
                break
        if not dominated:
            keep.append(points[i])
    return keep


def hypervolume(vals, ref) -> float:
    """Dominated hypervolume of objective rows against a reference corner.

    ``vals`` is (N, K) in canonical all-minimizing space (apply
    `objectives.canonical_signs` to max-direction axes first) and ``ref``
    the (K,) worst corner; the result is the exact volume of the union of
    boxes ``[v, ref]`` — the standard frontier-quality scalar the explore
    benchmark compares surrogate-guided search against exhaustive sweeps
    with.  Computed by recursive dimension-sweep slicing: exact for any
    K, O(N^2) per level, intended for frontier-sized sets (hundreds of
    points), not raw sweep clouds.  Rows with any non-finite coordinate
    or outside the reference box contribute nothing; dominated rows are
    harmless (their boxes are subsets).
    """
    ref = np.asarray(ref, dtype=np.float64).reshape(-1)
    v = np.asarray(vals, dtype=np.float64).reshape(-1, ref.shape[0])
    keep = np.all(np.isfinite(v), axis=1) & np.all(v < ref, axis=1)
    v = v[keep]
    if not v.size:
        return 0.0

    def hv(rows: np.ndarray, r: np.ndarray) -> float:
        if rows.shape[1] == 1:
            return float(r[0] - rows[:, 0].min())
        rows = rows[np.argsort(rows[:, 0], kind="stable")]
        total = 0.0
        for i in range(rows.shape[0]):
            hi = rows[i + 1, 0] if i + 1 < rows.shape[0] else r[0]
            width = hi - rows[i, 0]
            if width > 0.0:
                # slab [rows[i,0], hi): its cross-section is dominated by
                # exactly the points entered so far
                total += width * hv(rows[:i + 1, 1:], r[1:])
        return total

    return hv(v, ref)


# ---------------------------------------------------------------------------
# Device-resident streaming Pareto frontier (carried across chunks)
# ---------------------------------------------------------------------------

# Default capacity of the carried frontier state (number of non-dominated
# candidates held on device).  Real sweep frontiers are tiny next to the
# point count; overflow is detected and reported, never silent.
FRONTIER_CAPACITY = 512


def frontier_init(capacity: int, n_obj: int,
                  payload_dim: int) -> Tuple[jnp.ndarray, ...]:
    """Empty carried frontier state for `frontier_merge`.

    ``(vals, payload, idx, overflow)``: objective rows (+inf = empty slot),
    an opaque per-point payload (the raw metric rows, so surviving records
    can be rebuilt without ever materializing the full sweep), the global
    point index (-1 = empty), and a scalar count of finite candidates that
    were dropped because the frontier outgrew ``capacity``.
    """
    return (jnp.full((capacity, n_obj), jnp.inf, dtype=jnp.float32),
            jnp.zeros((capacity, payload_dim), dtype=jnp.float32),
            jnp.full((capacity,), -1, dtype=jnp.int32),
            jnp.zeros((), dtype=jnp.int32))


def frontier_merge(state: Tuple, vals: jnp.ndarray, payload: jnp.ndarray,
                   idx: jnp.ndarray) -> Tuple[jnp.ndarray, ...]:
    """One streaming-skyline step: merge a batch into the carried state.

    Pure jnp (traceable; the pipelined executor jits this fused behind the
    batched evaluation with the state buffers donated).  Dominance follows
    `pareto_front`: a candidate is dropped iff some other candidate is <=
    on all objectives and < on at least one; exact ties never dominate
    each other, and rows with any non-finite objective (infeasible points,
    padding, empty slots) never enter the frontier.  A carried point can
    still be evicted by a later batch — the state always holds the skyline
    of everything seen so far, truncated to capacity in full lexicographic
    order (all objectives, then global point index; ``overflow`` counts
    what the truncation dropped).  The full-lex key makes the kept set a
    canonical function of the surviving point set — independent of how
    points are arranged across state slots and batch rows — because a
    dominator always sorts strictly before anything it dominates, and the
    point index breaks exact-tie races deterministically.  (Which points
    *survive* can still depend on merge history once overflow drops a
    future dominator — any bounded streaming skyline has that limit, which
    is why ``overflow > 0`` flags the frontier as inexact and the
    cross-worker coordinator merges with the unbounded
    `frontier_merge_states` instead.)
    """
    svals, spay, sidx, overflow = state
    capacity = svals.shape[0]
    av = jnp.concatenate([svals, jnp.asarray(vals, dtype=jnp.float32)])
    ap = jnp.concatenate([spay, jnp.asarray(payload, dtype=jnp.float32)])
    ai = jnp.concatenate([sidx, jnp.asarray(idx, dtype=jnp.int32)])
    finite = jnp.all(jnp.isfinite(av), axis=1) & (ai >= 0)
    # pairwise dominance: dominated[i] iff some finite j <= i on all
    # objectives and < on one ((CAP+B)^2 x K ops — trivial on device)
    le = jnp.all(av[None, :, :] <= av[:, None, :], axis=-1)
    lt = jnp.any(av[None, :, :] < av[:, None, :], axis=-1)
    dominated = jnp.any(le & lt & finite[None, :], axis=1)
    keep = finite & ~dominated
    # survivors first in full lex order (objectives, then point index),
    # empties pushed to +inf / INT32_MAX; lexsort's primary key is LAST
    masked = jnp.where(keep[:, None], av, jnp.inf)
    idx_key = jnp.where(keep, ai, jnp.iinfo(jnp.int32).max)
    order = jnp.lexsort((idx_key,) + tuple(
        masked[:, k] for k in range(av.shape[1] - 1, -1, -1)))
    kept_beyond = jnp.sum(keep) - jnp.minimum(jnp.sum(keep), capacity)
    order = order[:capacity]
    mask = keep[order]
    return (jnp.where(mask[:, None], av[order], jnp.inf),
            jnp.where(mask[:, None], ap[order], 0.0),
            jnp.where(mask, ai[order], -1),
            overflow + kept_beyond.astype(jnp.int32))


def frontier_unpack(state: Tuple) -> Tuple[np.ndarray, np.ndarray,
                                           np.ndarray, int]:
    """Pull a carried frontier state to host -> (vals, payload, idx,
    n_overflowed) with empty slots stripped."""
    vals, payload, idx, overflow = (np.asarray(x) for x in state)
    live = idx >= 0
    return (vals[live].astype(np.float64), payload[live], idx[live],
            int(overflow))


def frontier_merge_states(a: Tuple, b: Tuple) -> Tuple[np.ndarray, ...]:
    """Merge two carried frontier states host-side — the coordinator's
    cross-worker reduction.

    Unlike the streaming `frontier_merge`, this merge is **unbounded**: it
    dedupes by global point index (the same point checkpointed by two
    incarnations of a worker is one point), drops dominated points with
    the exact f32 semantics of the device merge, and keeps EVERY survivor,
    growing the state instead of truncating to a capacity.  That makes the
    live set exactly commutative, associative, and idempotent — any merge
    order over any partition of worker states yields the same global
    frontier, which the fabric's property tests pin.  (A bounded merge
    cannot promise this: once truncation drops a not-yet-needed dominator,
    which points survive depends on merge history.  Workers' own overflow
    counters are summed through, so ``overflow > 0`` still flags that some
    worker's *local* frontier was inexact — the same contract as a
    single-host run.)

    Slot layout of the result is canonical: survivors in full
    lexicographic order (objectives, then point index), padded to the
    larger input's capacity.  States must agree on objective and payload
    dimensions (same sweep spec).
    """
    av, ap, ai, ao = (np.asarray(x) for x in a)
    bv, bp, bi, bo = (np.asarray(x) for x in b)
    if av.shape[1:] != bv.shape[1:] or ap.shape[1:] != bp.shape[1:]:
        raise ValueError(
            f"frontier states disagree on objective/payload shape: "
            f"{av.shape[1:]}/{ap.shape[1:]} vs {bv.shape[1:]}/"
            f"{bp.shape[1:]} — were they produced by the same spec?")
    vals = np.concatenate([av, bv]).astype(np.float32)
    pay = np.concatenate([ap, bp]).astype(np.float32)
    idx = np.concatenate([ai, bi]).astype(np.int32)
    live = (idx >= 0) & np.all(np.isfinite(vals), axis=1)
    # dedupe by global point index: re-merging a state that already holds
    # a point must be a no-op (the duplicate rows are the same evaluated
    # point, so which copy survives is immaterial)
    first: Dict[int, int] = {}
    for k in np.flatnonzero(live):
        first.setdefault(int(idx[k]), int(k))
    ks = np.asarray(sorted(first.values()), dtype=np.int64)
    n = len(ks)
    cap = max(av.shape[0], bv.shape[0], n)
    overflow = np.asarray(int(ao) + int(bo), dtype=np.int32)
    if n:
        v = vals[ks]
        le = np.all(v[None, :, :] <= v[:, None, :], axis=-1)
        lt = np.any(v[None, :, :] < v[:, None, :], axis=-1)
        dominated = np.any(le & lt, axis=1)
        ks = ks[~dominated]
        # canonical slot order: full lex (objectives, then point index)
        v = vals[ks]
        order = np.lexsort((idx[ks],) + tuple(
            v[:, k] for k in range(v.shape[1] - 1, -1, -1)))
        ks = ks[order]
        n = len(ks)
    out_v = np.full((cap, vals.shape[1]), np.inf, dtype=np.float32)
    out_p = np.zeros((cap, pay.shape[1]), dtype=np.float32)
    out_i = np.full((cap,), -1, dtype=np.int32)
    out_v[:n] = vals[ks]
    out_p[:n] = pay[ks]
    out_i[:n] = idx[ks]
    return out_v, out_p, out_i, overflow


# ---------------------------------------------------------------------------
# Design-space sweep driver
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SweepPoint:
    """One evaluated design point of a `sweep()`."""

    arch: str                       # model architecture id
    cell: str                       # shape cell name
    mesh: Tuple[int, ...]
    logic: str
    hbm: str
    net: str
    strategy: Strategy
    time_s: float
    compute_s: float
    comm_s: float
    exposed_comm_s: float
    devices: int
    power_w: float
    chip_area_mm2: float

    def metric(self, name: str) -> float:
        return float(getattr(self, name))

    def as_csv_row(self) -> str:
        return (f"{self.arch},{self.cell},{'x'.join(map(str, self.mesh))},"
                f"{self.logic},{self.hbm},{self.net},{self.strategy.name},"
                f"{self.time_s:.6e},{self.compute_s:.6e},{self.comm_s:.6e},"
                f"{self.devices},{self.power_w:g},{self.chip_area_mm2:g}")


CSV_HEADER = ("arch,cell,mesh,logic,hbm,net,strategy,time_s,compute_s,"
              "comm_s,devices,power_w,chip_area_mm2")


@dataclasses.dataclass
class SweepResult:
    points: List[SweepPoint]
    n_evaluations: int

    def pareto(self, objectives: Sequence[str] = ("time_s", "devices")
               ) -> List[SweepPoint]:
        objs = [(lambda p, k=k: p.metric(k)) for k in objectives]
        return pareto_front(self.points, objs)

    def best(self) -> SweepPoint:
        return min(self.points, key=lambda p: p.time_s)

    def to_csv(self) -> str:
        return "\n".join([CSV_HEADER] + [p.as_csv_row()
                                         for p in self.points])


def _default_strategies(cfg, cell, mesh_shape) -> List[Strategy]:
    from repro.core import planner     # lazy: planner imports pathfinder
    return planner.candidate_strategies(cfg, cell, mesh_shape)


def sweep(arches: Sequence[str], cells: Sequence[str],
          mesh_shapes: Sequence[Tuple[int, ...]],
          logic_nodes: Sequence[str] = ("N7",),
          hbms: Sequence[str] = ("HBM2E",),
          nets: Sequence[str] = ("IB-NDR-X8",),
          budgets: Optional[Budgets] = None,
          ppe: PPEConfig = PPEConfig(n_tilings=8),
          strategies_fn: Optional[Callable] = None,
          cache: Optional[PredictionCache] = DEFAULT_CACHE,
          profile=None) -> SweepResult:
    """Cross-product design-space sweep (the paper's §9 studies, batched).

    arches x cells define workload graphs, mesh_shapes define systems and
    candidate strategies, (logic, hbm, net) triples define AGE'd hardware.
    All hardware points sharing a skeleton are scored in one vmapped call.
    ``profile`` (a `repro.calibrate` profile / dict / path) anchors every
    hardware point and the PPE kernel overhead to measured efficiencies.
    """
    from repro.configs.base import SHAPE_CELLS, get_config
    from repro.core import lmgraph, techlib
    from repro.core.placement import mesh_system

    budgets = budgets or Budgets.default()
    strategies_fn = strategies_fn or _default_strategies
    if profile is not None:
        from repro.calibrate import profiles as profiles_lib
        profile = profiles_lib.coerce(profile)
        ppe = profiles_lib.ppe_with_profile(ppe, profile)

    tech_axis = list(itertools.product(logic_nodes, hbms, nets))
    hw_axis = []
    for logic, hbm, net in tech_axis:
        tech = techlib.make_tech_config(logic, hbm, net)
        hw = age_lib.generate(tech, budgets)
        if profile is not None:
            from repro.calibrate import profiles as profiles_lib
            hw = profiles_lib.apply_profile(hw, profile)
        hw_axis.append(((logic, hbm, net), hw))

    points: List[EvalPoint] = []
    labels: List[tuple] = []
    for arch_name in arches:
        cfg = get_config(arch_name)
        for cell_name in cells:
            cell = SHAPE_CELLS[cell_name]
            graph = lmgraph.build_graph(cfg, cell)
            for mesh in mesh_shapes:
                system = mesh_system(tuple(mesh))
                for st in strategies_fn(cfg, cell, tuple(mesh)):
                    for (logic, hbm, net), hw in hw_axis:
                        points.append(EvalPoint(hw, graph, st,
                                                system=system))
                        labels.append((arch_name, cell_name, tuple(mesh),
                                       logic, hbm, net, st))
    rows = evaluate(points=points, ppe=ppe, cache=cache)
    out = []
    for (arch_name, cell_name, mesh, logic, hbm, net, st), row in zip(labels,
                                                                      rows):
        out.append(SweepPoint(
            arch=arch_name, cell=cell_name, mesh=mesh, logic=logic, hbm=hbm,
            net=net, strategy=st, time_s=float(row[0]),
            compute_s=float(row[1]), comm_s=float(row[2]),
            exposed_comm_s=float(row[3]), devices=st.devices,
            power_w=float(budgets.power_w),
            chip_area_mm2=float(budgets.proc_chip_area_mm2)))
    return SweepResult(points=out, n_evaluations=len(out))
