"""Compile-ahead subsystem: cross-design bucketed executables + AOT service.

Cold-start sweeps on realistic multi-arch x multi-mesh x multi-strategy
grids are *compile-bound*: every distinct design group pays a lazy XLA
compile on the device stage's critical path, so wall time scales with
O(designs), not with evaluation work.  This module removes that scaling in
two coordinated layers:

1. **Cross-design bucketing.**  Design evaluation functions for different
   (mesh, strategy, tech) designs of the same scenario cell trace to
   jaxprs that are *structurally identical* — the designs differ only in
   the scalar literals and closed-over constants baked into the trace
   (mesh extents, link counts, coefficient tables).  `design_vector`
   traces a design's scalar function once, **canonicalizes** the jaxpr by
   abstracting every literal operand and constvar into a positional input
   slot, and fingerprints the remaining pure structure.  Designs with
   equal fingerprints share one `Bucket`; each design is reduced to a
   small packed coefficient vector (`DesignVector.packs`).  One compiled
   executable per (bucket, device layout) then serves *every* member
   design — O(shape-buckets) compiles instead of O(designs) — and because
   every backend (serial, pipeline, fabric workers) dispatches the *same*
   canonical executable, cross-backend records are bit-identical by
   construction (XLA cannot constant-fold per-design values it never
   sees).

2. **AOT compile service.**  `CompileService` is a small background
   thread pool that drives `wrapper.lower(avals).compile()` to completion
   off the critical path.  The pipeline producer submits the (key, input
   shape) pairs of upcoming superbatches while packing the current one;
   finished executables land in the entry's AOT table inside
   `pathfinder._COMPILED`, so the device stage only dispatches warm
   functions.  Submissions are deduped fleet-wide within the process (one
   compile per (key, signature)), submitted keys are pinned against LRU
   eviction until first dispatch, and a lookahead miss compiles inline
   (counted as `stall_seconds`).  A failed compile is raised by the
   dispatch that needs it.

Bucketing is on by default and is an execution-only change: chunk hashes,
point keys, record payloads, and frontier merges are unaffected.  Set env
``REPRO_NO_BUCKETING=1`` (or pass ``--no-bucketing`` / ``bucketed=False``)
to fall back to the legacy per-design closed-over compilation path, which
is numerically equivalent only to float32 rounding (~1e-7 relative).
"""

from __future__ import annotations

import collections
import dataclasses
import os
import queue
import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.core import ClosedJaxpr, Jaxpr, Literal, Var

from repro.core import pathfinder

__all__ = [
    "Bucket", "DesignVector", "design_vector", "batch_entry",
    "design_batch_fn", "bucketing_default", "set_bucketing_default",
    "bucket_stats", "CompileService", "service",
]


# ---------------------------------------------------------------------------
# Bucketing default (the --no-bucketing escape hatch)
# ---------------------------------------------------------------------------

_BUCKETING_DEFAULT = os.environ.get(
    "REPRO_NO_BUCKETING", "").lower() not in ("1", "true", "yes")


def bucketing_default() -> bool:
    """Whether canonical bucketed executables are used when callers don't
    say (env ``REPRO_NO_BUCKETING`` flips the process default)."""
    return _BUCKETING_DEFAULT


def set_bucketing_default(flag: bool) -> bool:
    """Set the process-wide bucketing default; returns the previous value."""
    global _BUCKETING_DEFAULT
    prev, _BUCKETING_DEFAULT = _BUCKETING_DEFAULT, bool(flag)
    return prev


def resolve_bucketed(flag: Optional[bool]) -> bool:
    return _BUCKETING_DEFAULT if flag is None else bool(flag)


# ---------------------------------------------------------------------------
# Jaxpr canonicalization
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Bucket:
    """One equivalence class of design functions: the canonical jaxpr with
    every closed-over constant and literal abstracted into coefficient
    slots, plus the slot -> packed-class indexing needed to rebind a
    member design's values at dispatch time."""

    id: int
    jaxpr: Jaxpr                   # constvars=[]; invars = coeffs + data
    classes: Tuple[tuple, ...]     # (dtype_str, shape) per coeff pack
    class_sizes: Tuple[int, ...]
    slots: Tuple[Tuple[int, int], ...]  # per coeff invar: (class, index)
    n_data: int                    # trailing data invars
    n_outs: int

    def scalar_fn(self) -> Callable:
        """(packs_tuple, *data) -> outputs, replaying the canonical jaxpr.

        ``packs_tuple[c]`` stacks this design's class-``c`` coefficients as
        one ``(class_sizes[c], *shape)`` array; slots are statically
        indexed out, so the whole rebind traces to gathers and the XLA
        program stays one executable for every bucket member.
        """
        jaxpr, slots = self.jaxpr, self.slots

        def scalar(packs, *data):
            coeffs = [packs[c][i] for c, i in slots]
            out = jax.core.eval_jaxpr(jaxpr, [], *coeffs, *data)
            return out[0] if len(out) == 1 else tuple(out)

        return scalar


@dataclasses.dataclass(frozen=True)
class DesignVector:
    """A design reduced to (shared bucket, packed per-design coefficients)."""

    bucket: Bucket
    packs: Tuple[np.ndarray, ...]  # aligned with bucket.classes

    def broadcast_packs(self, lead: Tuple[int, ...]) -> Tuple[np.ndarray, ...]:
        """Replicate the coefficient packs across leading batch dims."""
        return tuple(np.broadcast_to(p, tuple(lead) + p.shape)
                     for p in self.packs)


def _aval_sig(aval) -> tuple:
    return (str(getattr(aval, "dtype", aval)),
            tuple(getattr(aval, "shape", ())),
            bool(getattr(aval, "weak_type", False)))


def _hashable(x):
    if isinstance(x, (Jaxpr, ClosedJaxpr)):
        return ("jaxpr", repr(x))
    if isinstance(x, dict):
        return tuple(sorted((k, _hashable(v)) for k, v in x.items()))
    if isinstance(x, (list, tuple)):
        return (type(x).__name__,) + tuple(_hashable(v) for v in x)
    if isinstance(x, np.ndarray):
        return ("nd", x.shape, str(x.dtype), x.tobytes())
    try:
        hash(x)
        return x
    except TypeError:
        return repr(x)


def _canonicalize(closed: ClosedJaxpr):
    """Abstract literals/constvars out of a closed jaxpr.

    Returns ``(jaxpr, coeff_vals, coeff_avals, fingerprint)`` where
    ``jaxpr`` has ``constvars=[]`` and ``invars = [constvar slots...,
    literal slots..., original invars...]``; ``coeff_vals`` holds this
    design's values for the coefficient invars in order; ``fingerprint``
    is a hashable tuple of the remaining structure — identical fingerprints mean
    the canonical jaxprs are interchangeable up to coefficient values.
    """
    jaxpr = closed.jaxpr
    var_ids: Dict[object, int] = {}

    def vid(v) -> int:
        i = var_ids.get(v)
        if i is None:
            i = var_ids[v] = len(var_ids)
        return i

    for cv in jaxpr.constvars:
        vid(cv)
    for iv in jaxpr.invars:
        vid(iv)

    lit_vars: List[Var] = []
    lit_vals: List[np.ndarray] = []
    lit_avals: List[object] = []
    new_eqns = []
    fp_eqns: List[tuple] = []
    for eqn in jaxpr.eqns:
        invars = []
        fp_in = []
        changed = False
        for a in eqn.invars:
            if isinstance(a, Literal):
                aval = a.aval
                var = Var(aval)
                lit_vars.append(var)
                lit_vals.append(np.asarray(a.val))
                lit_avals.append(aval)
                invars.append(var)
                fp_in.append(("l", _aval_sig(aval)))
                changed = True
            else:
                invars.append(a)
                fp_in.append(("v", vid(a)))
        out_ids = tuple(vid(v) for v in eqn.outvars)
        fp_eqns.append((eqn.primitive.name, _hashable(eqn.params),
                        tuple(fp_in), out_ids))
        new_eqns.append(eqn.replace(invars=invars) if changed else eqn)

    coeff_avals = [v.aval for v in jaxpr.constvars] + lit_avals
    coeff_vals = [np.asarray(c) for c in closed.consts] + lit_vals
    fp_out = tuple(
        ("l", _aval_sig(v.aval)) if isinstance(v, Literal)
        else ("v", var_ids.get(v, -1)) for v in jaxpr.outvars)
    fingerprint = (
        tuple(_aval_sig(v.aval) for v in jaxpr.constvars),
        tuple(_aval_sig(v.aval) for v in jaxpr.invars),
        tuple(fp_eqns), fp_out,
    )
    # the coefficient slots widen the invar list: name them in front of
    # the traced function's own argument names
    n_coeff = len(jaxpr.constvars) + len(lit_vars)
    dbg = jaxpr.debug_info
    debug_info = dbg._replace(arg_names=tuple(
        f"coeff[{i}]" for i in range(n_coeff))
        + tuple(dbg.safe_arg_names(len(jaxpr.invars))))
    canonical = jaxpr.replace(
        constvars=[], eqns=new_eqns, debug_info=debug_info,
        invars=list(jaxpr.constvars) + lit_vars + list(jaxpr.invars))
    return canonical, coeff_vals, coeff_avals, fingerprint


def _pack(coeff_vals, coeff_avals):
    """Group coefficient slots by (dtype, shape) and stack the values.

    Slot -> class assignment is purely structural (derived from the
    coefficient aval sequence, which the fingerprint covers), so every
    bucket member maps slots to pack positions identically.
    """
    classes: List[tuple] = []
    class_pos: Dict[tuple, int] = {}
    members: List[List[int]] = []
    slots: List[Tuple[int, int]] = []
    for i, aval in enumerate(coeff_avals):
        ck = (str(aval.dtype), tuple(aval.shape))
        c = class_pos.get(ck)
        if c is None:
            c = class_pos[ck] = len(classes)
            classes.append(ck)
            members.append([])
        slots.append((c, len(members[c])))
        members[c].append(i)
    packs = []
    for c, ck in enumerate(classes):
        dtype = np.dtype(ck[0])
        packs.append(np.stack(
            [np.asarray(coeff_vals[i], dtype=dtype) for i in members[c]]))
    return tuple(classes), tuple(len(m) for m in members), \
        tuple(slots), tuple(packs)


# ---------------------------------------------------------------------------
# Registries (process-wide, shared by every backend)
# ---------------------------------------------------------------------------

_REG_LOCK = threading.Lock()
_BUCKETS: Dict[tuple, Bucket] = {}          # fingerprint -> bucket
_DESIGNS: "collections.OrderedDict[tuple, DesignVector]" = \
    collections.OrderedDict()
_DESIGNS_MAXSIZE = 4096
_STATS = {"designs_traced": 0, "buckets": 0}


def _clear_registries() -> None:
    with _REG_LOCK:
        _BUCKETS.clear()
        _DESIGNS.clear()
        _STATS["designs_traced"] = 0
        _STATS["buckets"] = 0


def bucket_stats() -> Dict[str, int]:
    """How many designs have been canonicalized and into how many buckets
    they collapsed (`buckets` << `designs_traced` is the win)."""
    with _REG_LOCK:
        return dict(_STATS, designs_registered=len(_DESIGNS))


def design_vector(design_key: tuple, make_scalar: Callable[[], Callable],
                  in_avals: Sequence[jax.ShapeDtypeStruct]) -> DesignVector:
    """Trace + canonicalize a design's scalar function (memoized).

    ``design_key`` identifies the design process-wide (the same keys used
    for the legacy per-design compiled store), ``make_scalar`` builds the
    scalar function to trace, ``in_avals`` are its data input avals.
    Tracing happens outside the registry lock (it is the expensive step);
    a concurrent duplicate trace is resolved at intern time.
    """
    with _REG_LOCK:
        dv = _DESIGNS.get(design_key)
        if dv is not None:
            _DESIGNS.move_to_end(design_key)
            return dv
    closed = jax.make_jaxpr(make_scalar())(*[
        jnp.zeros(a.shape, a.dtype) for a in in_avals])
    canonical, coeff_vals, coeff_avals, fp = _canonicalize(closed)
    classes, sizes, slots, packs = _pack(coeff_vals, coeff_avals)
    with _REG_LOCK:
        dv = _DESIGNS.get(design_key)
        if dv is not None:
            _DESIGNS.move_to_end(design_key)
            return dv
        bucket = _BUCKETS.get(fp)
        if bucket is None:
            bucket = Bucket(id=len(_BUCKETS), jaxpr=canonical,
                            classes=classes, class_sizes=sizes, slots=slots,
                            n_data=len(in_avals),
                            n_outs=len(canonical.outvars))
            _BUCKETS[fp] = bucket
            _STATS["buckets"] += 1
        _STATS["designs_traced"] += 1
        dv = DesignVector(bucket=bucket, packs=packs)
        _DESIGNS[design_key] = dv
        while len(_DESIGNS) > _DESIGNS_MAXSIZE:
            _DESIGNS.popitem(last=False)
        return dv


def bucket_builder(bucket: Bucket, n_dev: int = 1) -> Callable:
    """Build closure for a bucket's vmapped (``n_dev > 1``: pmapped)
    lazy wrapper — shared by `batch_entry` and the AOT prefetch path."""
    def build():
        inner = jax.vmap(bucket.scalar_fn())
        return jax.pmap(inner) if n_dev > 1 else jax.jit(inner)
    return build


def batch_entry(bucket: Bucket, n_dev: int = 1) -> "pathfinder.CompiledEntry":
    """The process-wide compiled entry for a bucket's vmapped executable.

    ``n_dev > 1`` wraps in `jax.pmap` (leading device axis); the entry
    lives in `pathfinder._COMPILED` under ``("cabucket", id, n_dev)`` so
    hit/miss/AOT accounting and LRU policy are shared with every other
    compiled function.
    """
    return pathfinder.compiled_entry(("cabucket", bucket.id, n_dev),
                                     bucket_builder(bucket, n_dev))


def design_batch_fn(design_key: tuple, make_scalar: Callable[[], Callable],
                    in_avals: Sequence[jax.ShapeDtypeStruct],
                    n_dev: int = 1) -> Callable:
    """Batched canonical dispatch for a single design.

    Returns ``fn(hw)`` accepting a batch of the design's (single) data
    input with 1 (jit) or 2 (pmap) leading batch dims; the design's
    coefficient packs are broadcast across the batch so the executable is
    the shared per-row bucket program (bit-identical to megabatched
    dispatch of the same bucket).
    """
    dv = design_vector(design_key, make_scalar, in_avals)
    entry = batch_entry(dv.bucket, n_dev)
    data_ndim = len(in_avals[0].shape)

    def fn(hw):
        lead = tuple(hw.shape[:hw.ndim - data_ndim])
        return entry(dv.broadcast_packs(lead), hw)

    return fn


# ---------------------------------------------------------------------------
# AOT compile service
# ---------------------------------------------------------------------------


class CompileService:
    """Background thread pool driving `.lower().compile()` off-path.

    `warm` registers (or fetches) a `CompiledEntry` and queues an AOT
    compile for one input-shape signature.  Dedupe is fleet-wide within
    the process: a (key, signature) already finished, in flight, or
    queued is not submitted again.  Every queued submission pins its
    store key (`pathfinder.pin_compiled`) so the LRU cannot evict the
    entry between build and first dispatch; the *dispatcher* releases the
    pin after first use (see `PipelineExecutor`), which is why `warm`
    reports whether it pinned.
    """

    def __init__(self, workers: Optional[int] = None):
        if workers is None:
            workers = int(os.environ.get("REPRO_COMPILE_WORKERS", "2"))
        self.workers = max(1, workers)
        self._q: "queue.Queue" = queue.Queue()
        self._lock = threading.Lock()
        self._pending: set = set()
        self._threads: List[threading.Thread] = []
        self._started = False

    def _ensure_threads(self) -> None:
        with self._lock:
            if self._started:
                return
            self._started = True
            for i in range(self.workers):
                t = threading.Thread(target=self._worker,
                                     name=f"compile-ahead-{i}", daemon=True)
                t.start()
                self._threads.append(t)

    def _worker(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                self._q.put(None)  # let sibling workers drain out too
                return
            entry, args, key, sig = item
            try:
                entry.compile_for(args)
            except Exception:
                pass  # kept on the entry; the dispatch that needs it raises
            finally:
                with self._lock:
                    self._pending.discard((key, sig))

    def warm(self, key: tuple, build_wrapper: Callable[[], Callable],
             example_args: tuple) -> bool:
        """Queue an AOT compile of ``key`` for ``example_args``' shapes.

        ``example_args`` may be concrete arrays or `ShapeDtypeStruct`
        pytrees.  Returns True when a submission was queued (and the key
        pinned — the caller owes one `pathfinder.unpin_compiled(key)`
        after first dispatch), False when it was already warm/in flight.
        """
        entry = pathfinder.compiled_entry(key, build_wrapper)
        sig = entry.signature(example_args)
        with self._lock:
            if (key, sig) in self._pending or sig in entry.aot:
                return False
            self._pending.add((key, sig))
        pathfinder.pin_compiled(key)
        self._ensure_threads()
        self._q.put((entry, example_args, key, sig))
        return True

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Block until every queued compile finished (tests/benchmarks)."""
        import time as _time
        deadline = None if timeout is None else _time.monotonic() + timeout
        while True:
            with self._lock:
                if not self._pending:
                    return True
            if deadline is not None and _time.monotonic() > deadline:
                return False
            _time.sleep(0.005)

    def shutdown(self) -> None:
        if self._started:
            self._q.put(None)


_SERVICE: Optional[CompileService] = None
_SERVICE_LOCK = threading.Lock()


def service() -> CompileService:
    """The process-wide compile service (workers via REPRO_COMPILE_WORKERS;
    fabric worker processes each get their own, inherited through this
    module the same way `pathfinder._COMPILED` is)."""
    global _SERVICE
    with _SERVICE_LOCK:
        if _SERVICE is None:
            _SERVICE = CompileService()
        return _SERVICE
