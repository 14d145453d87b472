"""Microbenchmark harness — measured wall times of the repo's own kernels.

Every measurement times an executable that already exists in the repo:

  gemm         jit'd ``jnp.dot`` (XLA, the fig-6 methodology)
  gemm_pallas  the block-tiled Pallas GEMM (`repro.kernels.gemm`; compiled
               on a TPU, interpret mode on CPU)
  elementwise  a jit'd saxpy (the PPE's vector/bandwidth path)
  collective   `repro.parallel.collectives.bucketed_psum` under a
               multi-device `shard_map`: in-process over the chips present
               on an accelerator (refused with too few), through a
               forced-device CPU subprocess on a CPU host with a single
               device (the device count is fixed at first JAX init)
  train_step / prefill
               end-to-end jit'd steps of the `repro.models` families at
               smoke size (`configs.base.reduced`)
  decode_step  one-token jit'd `Model.decode_step` over a full KV cache
               at smoke size — the KV-cache-READ-bound step that anchors
               the model's main-memory bandwidth path (the decode graph's
               attention GEMMs charge the whole context per token)

Measurements stream to ``measurements.jsonl`` with the sweep runner's
fingerprint/resume discipline: ``spec.json`` pins the enumerated point set
(`MeasureSpec.fingerprint`), each finished point appends one JSONL record,
and a resumed run skips every key already on disk with zero re-measurement
(crash-torn tail lines are dropped by the shared `_iter_jsonl` reader).

The records feed `repro.calibrate.fitting` (parameter fit) and
`repro.calibrate.report` (validation tables).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import subprocess
import sys
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.sweeprunner import _iter_jsonl, json_safe

SPEC_VERSION = 1

# measurement kinds, in enumeration order
KINDS = ("gemm", "gemm_pallas", "elementwise", "collective",
         "train_step", "prefill", "decode_step")


# ---------------------------------------------------------------------------
# Specification (fully serializable — the resume identity)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MeasureSpec:
    """Everything that determines the measurement point set."""

    suite: str = "quick"
    gemm_shapes: Tuple[Tuple[int, int, int], ...] = ()
    gemm_dtype_bytes: int = 4
    pallas_shapes: Tuple[Tuple[int, int, int], ...] = ()
    elementwise_sizes: Tuple[int, ...] = ()
    collective_bytes: Tuple[int, ...] = ()
    collective_devices: int = 2
    model_archs: Tuple[str, ...] = ()
    model_phases: Tuple[str, ...] = ("train_step", "prefill")
    model_seq: int = 128
    model_batch: int = 2
    reps: int = 3
    warmup: int = 1

    def to_dict(self) -> Dict:
        d = dataclasses.asdict(self)
        d["gemm_shapes"] = [list(s) for s in self.gemm_shapes]
        d["pallas_shapes"] = [list(s) for s in self.pallas_shapes]
        for k in ("elementwise_sizes", "collective_bytes", "model_archs",
                  "model_phases"):
            d[k] = list(d[k])
        return d

    @staticmethod
    def from_dict(d: Dict) -> "MeasureSpec":
        d = dict(d)
        for k in ("gemm_shapes", "pallas_shapes"):
            d[k] = tuple(tuple(int(x) for x in s) for s in d.get(k) or ())
        for k in ("elementwise_sizes", "collective_bytes"):
            d[k] = tuple(int(x) for x in d.get(k) or ())
        for k in ("model_archs", "model_phases"):
            d[k] = tuple(d.get(k) or ())
        return MeasureSpec(**d)

    def fingerprint(self) -> str:
        blob = json.dumps(self.to_dict(), sort_keys=True)
        return hashlib.sha256(blob.encode()).hexdigest()[:16]


def default_spec(suite: str = "quick", reps: int = 3) -> MeasureSpec:
    """The standard suites.

    quick  GEMM-only (the CI calibrate-smoke lane and the acceptance
           sweep): seconds of wall time, enough signal to anchor compute
           throughput, memory bandwidth, and kernel overhead.
    full   adds the Pallas GEMM (tiny shapes: interpreted on CPU),
           elementwise/bandwidth probes, forced-2-device `bucketed_psum`
           collectives, and end-to-end model-family steps.
    """
    gemm = tuple(
        (m, n, k)
        for m in (128, 256, 512, 1024)
        for n, k in ((m, m), (m, 2 * m))
    ) + ((256, 1024, 512), (1024, 256, 2048))
    if suite == "quick":
        return MeasureSpec(suite="quick", gemm_shapes=gemm, reps=reps)
    if suite == "full":
        return MeasureSpec(
            suite="full", gemm_shapes=gemm,
            pallas_shapes=((128, 128, 128), (256, 256, 256)),
            elementwise_sizes=(1 << 16, 1 << 20, 1 << 23),
            collective_bytes=(1 << 16, 1 << 20, 1 << 22),
            model_archs=("qwen1.5-0.5b", "xlstm-125m", "recurrentgemma-2b"),
            model_phases=("train_step", "prefill", "decode_step"),
            reps=reps)
    raise ValueError(f"unknown suite {suite!r}; expected quick|full")


# ---------------------------------------------------------------------------
# Point enumeration
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MeasurePoint:
    """One enumerated measurement (strings/ints only — checkpointable)."""

    kind: str
    params: Tuple[Tuple[str, object], ...]   # sorted (name, value) pairs

    def get(self, name: str, default=None):
        return dict(self.params).get(name, default)

    def key(self) -> str:
        parts = [f"{k}={v}" for k, v in self.params]
        return "|".join([self.kind] + parts)


def _pt(kind: str, **params) -> MeasurePoint:
    return MeasurePoint(kind=kind, params=tuple(sorted(params.items())))


def enumerate_points(spec: MeasureSpec) -> List[MeasurePoint]:
    """Deterministic measurement point set for one spec."""
    pts: List[MeasurePoint] = []
    for m, n, k in spec.gemm_shapes:
        pts.append(_pt("gemm", m=m, n=n, k=k,
                       dtype_bytes=spec.gemm_dtype_bytes))
    for m, n, k in spec.pallas_shapes:
        pts.append(_pt("gemm_pallas", m=m, n=n, k=k,
                       dtype_bytes=spec.gemm_dtype_bytes))
    for n in spec.elementwise_sizes:
        pts.append(_pt("elementwise", n_elems=n))
    for b in spec.collective_bytes:
        pts.append(_pt("collective", bytes=b,
                       devices=spec.collective_devices))
    for arch in spec.model_archs:
        for phase in spec.model_phases:
            pts.append(_pt(phase, arch=arch, seq=spec.model_seq,
                           batch=spec.model_batch))
    return pts


# ---------------------------------------------------------------------------
# Timing primitives
# ---------------------------------------------------------------------------


def _time_fn(fn: Callable, warmup: int, reps: int) -> Tuple[float, float]:
    """(best, mean) wall seconds of ``fn()`` (must block until ready)."""
    for _ in range(max(warmup, 1)):
        fn()
    ts = []
    for _ in range(max(reps, 1)):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return min(ts), sum(ts) / len(ts)


def _measure_gemm(pt: MeasurePoint, spec: MeasureSpec) -> Dict:
    import jax
    import jax.numpy as jnp
    m, n, k = pt.get("m"), pt.get("n"), pt.get("k")
    db = int(pt.get("dtype_bytes", 4))
    dtype = jnp.float32 if db == 4 else jnp.bfloat16
    x = jnp.ones((m, k), dtype)
    w = jnp.ones((k, n), dtype)
    f = jax.jit(jnp.dot)
    best, mean = _time_fn(lambda: f(x, w).block_until_ready(),
                          spec.warmup, spec.reps)
    return {"flops": 2.0 * m * n * k, "bytes": float((m * k + k * n + m * n)
                                                     * db),
            "t_s": best, "t_mean_s": mean}


def _measure_gemm_pallas(pt: MeasurePoint, spec: MeasureSpec) -> Dict:
    from repro.kernels import ops
    import jax.numpy as jnp
    m, n, k = pt.get("m"), pt.get("n"), pt.get("k")
    db = int(pt.get("dtype_bytes", 4))
    dtype = jnp.float32 if db == 4 else jnp.bfloat16
    x = jnp.ones((m, k), dtype)
    w = jnp.ones((k, n), dtype)

    def run():
        ops.matmul(x, w, use_pallas=True).block_until_ready()
    best, mean = _time_fn(run, spec.warmup, spec.reps)
    return {"flops": 2.0 * m * n * k,
            "bytes": float((m * k + k * n + m * n) * db),
            "t_s": best, "t_mean_s": mean}


def _measure_elementwise(pt: MeasurePoint, spec: MeasureSpec) -> Dict:
    import jax
    import jax.numpy as jnp
    n = int(pt.get("n_elems"))
    a = jnp.ones((n,), jnp.float32)
    b = jnp.ones((n,), jnp.float32)
    f = jax.jit(lambda a, b: a * 1.5 + b)
    best, mean = _time_fn(lambda: f(a, b).block_until_ready(),
                          spec.warmup, spec.reps)
    return {"flops": 2.0 * n, "bytes": 3.0 * n * 4,
            "t_s": best, "t_mean_s": mean}


_COLLECTIVE_SNIPPET = """
import json, sys
from repro.calibrate import microbench
spec = microbench.MeasureSpec.from_dict(json.loads(sys.argv[1]))
wanted = set(json.loads(sys.argv[2]))
for pt in microbench.enumerate_points(spec):
    if pt.kind != "collective" or pt.key() not in wanted:
        continue
    rec = microbench.measure_point(pt, spec)
    print("MEASURE:" + json.dumps(microbench.json_safe(rec)), flush=True)
"""


def _measure_collective(pt: MeasurePoint, spec: MeasureSpec) -> Dict:
    """`bucketed_psum` of a payload tree under multi-device shard_map.

    Requires >= ``devices`` JAX devices in-process; `run_points` routes
    the whole collective group through a forced-device subprocess when a
    CPU parent is single-device (the XLA device count is fixed at first
    init).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.parallel import collectives

    n_dev = int(pt.get("devices", 2))
    if jax.local_device_count() < n_dev:
        raise RuntimeError(
            f"collective point needs {n_dev} devices, have "
            f"{jax.local_device_count()} (run via subprocess)")
    payload_bytes = int(pt.get("bytes"))
    n = max(payload_bytes // 4, 1)
    tree = {"g": jnp.ones((n,), jnp.float32)}
    mesh = Mesh(np.asarray(jax.devices()[:n_dev]), ("x",))

    @jax.jit
    def reduce(t):
        return jax.shard_map(
            lambda tt: collectives.bucketed_psum(tt, "x"),
            mesh=mesh, in_specs=(P(),), out_specs=P())(t)

    best, mean = _time_fn(
        lambda: jax.block_until_ready(reduce(tree)), spec.warmup, spec.reps)
    return {"flops": 0.0, "bytes": float(payload_bytes), "t_s": best,
            "t_mean_s": mean}


# smoke-size shape cell used for model-step measurements; the prediction
# side builds its lmgraph from the identical (reduced cfg, cell) pair
_CELL_KINDS = {"train_step": "train", "prefill": "prefill",
               "decode_step": "decode"}


def model_cell(pt: MeasurePoint):
    from repro.configs.base import ShapeCell
    kind = _CELL_KINDS[pt.kind]
    return ShapeCell(f"cal_{kind}", int(pt.get("seq")),
                     int(pt.get("batch")), kind)


def _measure_model(pt: MeasurePoint, spec: MeasureSpec) -> Dict:
    import jax
    import jax.numpy as jnp

    from repro.configs.base import get_config, reduced
    from repro.models import build_model

    cfg = reduced(get_config(str(pt.get("arch"))))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    seq, batch = int(pt.get("seq")), int(pt.get("batch"))
    tokens = jnp.zeros((batch, seq), jnp.int32)
    batch_d = {"tokens": tokens, "labels": tokens}
    if cfg.is_encoder_decoder:
        frames = jnp.zeros((batch, seq, cfg.d_model), jnp.float32)
        batch_d = {"frames": frames, "tokens": tokens[:, :cfg.decoder_len],
                   "labels": tokens[:, :cfg.decoder_len]}

    if pt.kind == "train_step":
        def loss(p):
            out = model.loss_fn(p, batch_d)
            return out[0] if isinstance(out, tuple) else out
        step = jax.jit(jax.grad(loss))
        run = lambda: jax.block_until_ready(step(params))
    else:                                       # prefill = one forward pass
        fwd = jax.jit(lambda p: model.forward(p, batch_d))
        run = lambda: jax.block_until_ready(fwd(params))
    best, mean = _time_fn(run, spec.warmup, spec.reps)
    return {"flops": 0.0, "bytes": 0.0, "t_s": best, "t_mean_s": mean}


def _measure_decode(pt: MeasurePoint, spec: MeasureSpec) -> Dict:
    """One-token decode over a FULL KV cache (pos = seq-1): the measured
    step is KV-cache-read-bound — attention reads the whole context per
    token — anchoring the dram-bandwidth path the serving scenarios lean
    on (the ROADMAP's missing decode-phase calibration depth)."""
    import jax
    import jax.numpy as jnp

    from repro.configs.base import get_config, reduced
    from repro.core.scenarios import kv_cache_bytes
    from repro.models import build_model

    cfg = reduced(get_config(str(pt.get("arch"))))
    model = build_model(cfg)
    if model.decode_step is None or model.init_cache is None:
        raise RuntimeError(f"{cfg.name}: model family has no decode path")
    params = model.init(jax.random.PRNGKey(0))
    seq, batch = int(pt.get("seq")), int(pt.get("batch"))
    caches = model.init_cache(batch, seq)
    tokens = jnp.zeros((batch, 1), jnp.int32)
    step = jax.jit(lambda p, c, t, pos: model.decode_step(p, c, t, pos))
    pos = jnp.asarray(seq - 1, jnp.int32)     # read the whole context
    run = lambda: jax.block_until_ready(step(params, caches, tokens, pos))
    best, mean = _time_fn(run, spec.warmup, spec.reps)
    return {"flops": 0.0, "bytes": float(kv_cache_bytes(cfg, seq, batch)),
            "t_s": best, "t_mean_s": mean}


_MEASURERS: Dict[str, Callable[[MeasurePoint, MeasureSpec], Dict]] = {
    "gemm": _measure_gemm,
    "gemm_pallas": _measure_gemm_pallas,
    "elementwise": _measure_elementwise,
    "collective": _measure_collective,
    "train_step": _measure_model,
    "prefill": _measure_model,
    "decode_step": _measure_decode,
}


def measure_point(pt: MeasurePoint, spec: MeasureSpec) -> Dict:
    """Measure one point -> JSONL record (label fields + timings)."""
    data = _MEASURERS[pt.kind](pt, spec)
    return {"key": pt.key(), "kind": pt.kind, **dict(pt.params),
            "reps": spec.reps, **data}


def _collective_subprocess(spec: MeasureSpec,
                           keys: Sequence[str]) -> List[Dict]:
    """Run the *pending* collective points (by key) in a forced-device
    child process — already-persisted points are never re-measured, the
    same zero-re-measurement discipline as the in-process path."""
    import repro
    env = dict(os.environ)
    # repro is a namespace package (no __init__.py): locate via __path__
    src = os.path.dirname(os.path.abspath(list(repro.__path__)[0]))
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (src, env.get("PYTHONPATH", "")) if p)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count="
                          f"{spec.collective_devices}").strip()
    proc = subprocess.run(
        [sys.executable, "-c", _COLLECTIVE_SNIPPET,
         json.dumps(spec.to_dict()), json.dumps(list(keys))],
        env=env, capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        raise RuntimeError(f"collective subprocess failed: {proc.stderr}")
    out = []
    for line in proc.stdout.splitlines():
        if line.startswith("MEASURE:"):
            out.append(json.loads(line[len("MEASURE:"):]))
    return out


def run_points(points: Sequence[MeasurePoint], spec: MeasureSpec,
               on_record: Callable[[Dict], None],
               verbose: bool = False) -> int:
    """Measure ``points`` in order, invoking ``on_record`` per record.

    Collective points need ``spec.collective_devices`` devices.  When the
    process has fewer, a CPU host measures them in one forced-device CPU
    subprocess; an accelerator host refuses them (a CPU child would time
    the host, not the chip) and says so.  Everything else runs in-process.
    """
    import jax
    n = 0
    need_sub = [p for p in points if p.kind == "collective"] \
        if jax.local_device_count() < spec.collective_devices else []
    sub_keys = {p.key() for p in need_sub}
    if need_sub and jax.default_backend() != "cpu":
        print(f"# refused {len(need_sub)} collective points: they need "
              f"{spec.collective_devices} {jax.default_backend()} devices "
              f"and this process has {jax.local_device_count()}",
              file=sys.stderr, flush=True)
    elif need_sub:
        for rec in _collective_subprocess(spec, sorted(sub_keys)):
            if rec["key"] in sub_keys:
                on_record(rec)
                n += 1
                if verbose:
                    print(f"# measured {rec['key']}: "
                          f"{rec['t_s'] * 1e6:.1f} us", flush=True)
    for pt in points:
        if pt.key() in sub_keys:
            continue
        rec = measure_point(pt, spec)
        on_record(rec)
        n += 1
        if verbose:
            print(f"# measured {rec['key']}: {rec['t_s'] * 1e6:.1f} us",
                  flush=True)
    return n


# ---------------------------------------------------------------------------
# The runner (spec.json + measurements.jsonl, resumable)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class MeasureStats:
    n_points_total: int
    n_skipped: int
    n_measured: int
    elapsed_s: float
    out_dir: Optional[str]
    records: List[Dict]


class MicrobenchRunner:
    """Streams measurements to ``out_dir`` with resume discipline.

    Layout:
      spec.json           {"version", "fingerprint", "spec": {...}}
      measurements.jsonl  one record per measured point

    A resumed run must present the identical spec (fingerprint-checked)
    and re-measures nothing already on disk.
    """

    def __init__(self, spec: MeasureSpec, out_dir: Optional[str] = None):
        self.spec = spec
        self.out_dir = out_dir
        self._fp = spec.fingerprint()

    @staticmethod
    def from_dir(out_dir: str) -> "MicrobenchRunner":
        with open(os.path.join(out_dir, "spec.json")) as fh:
            head = json.load(fh)
        return MicrobenchRunner(MeasureSpec.from_dict(head["spec"]),
                                out_dir=out_dir)

    def _paths(self):
        return (os.path.join(self.out_dir, "spec.json"),
                os.path.join(self.out_dir, "measurements.jsonl"))

    def existing(self) -> Dict[str, Dict]:
        """Records already streamed (torn tail lines dropped)."""
        if self.out_dir is None:
            return {}
        _, mpath = self._paths()
        return {r["key"]: r for r in _iter_jsonl(mpath) if "key" in r}

    def run(self, resume: bool = False, verbose: bool = False
            ) -> MeasureStats:
        t0 = time.perf_counter()
        points = enumerate_points(self.spec)
        done: Dict[str, Dict] = {}
        fh = None
        records: List[Dict] = []
        if self.out_dir is not None:
            os.makedirs(self.out_dir, exist_ok=True)
            spec_path, mpath = self._paths()
            if os.path.exists(spec_path):
                with open(spec_path) as f:
                    head = json.load(f)
                if head.get("fingerprint") != self._fp:
                    raise ValueError(
                        f"cannot reuse {self.out_dir}: measurement spec "
                        f"changed (was {head.get('fingerprint')}, now "
                        f"{self._fp}); point --out at a fresh directory")
                if not resume and os.path.exists(mpath):
                    raise FileExistsError(
                        f"{self.out_dir} already holds measurements; pass "
                        f"resume=True (CLI: --resume) to continue, or use "
                        f"a fresh directory")
            if resume:
                done = self.existing()
            tmp = spec_path + ".tmp"
            with open(tmp, "w") as f:
                json.dump({"version": SPEC_VERSION, "fingerprint": self._fp,
                           "spec": self.spec.to_dict()}, f, indent=2)
            os.replace(tmp, spec_path)
            fh = open(mpath, "a")
        elif resume:
            raise ValueError("resume=True requires an out_dir")

        pending = [p for p in points if p.key() not in done]

        def commit(rec: Dict):
            records.append(rec)
            if fh is not None:
                fh.write(json.dumps(json_safe(rec)) + "\n")
                fh.flush()

        try:
            n = run_points(pending, self.spec, commit, verbose=verbose)
        finally:
            if fh is not None:
                fh.close()
        return MeasureStats(
            n_points_total=len(points), n_skipped=len(done), n_measured=n,
            elapsed_s=time.perf_counter() - t0, out_dir=self.out_dir,
            records=list(done.values()) + records)


def load_measurements(out_dir: str) -> List[Dict]:
    """All measurement records streamed into ``out_dir``, spec order."""
    runner = MicrobenchRunner.from_dir(out_dir)
    by_key = runner.existing()
    return [by_key[p.key()] for p in enumerate_points(runner.spec)
            if p.key() in by_key]
