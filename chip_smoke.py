#!/usr/bin/env python3
"""Run the main path once on a TPU and check what comes out.

    python chip_smoke.py             # one chip: every phase below
    python chip_smoke.py --chips 4   # four chips: the sharded paths only

One chip.  Each phase prints one line and raises on any failure:

  device    the first JAX device is a TPU
  sweep     `pathfind sweep` (pipeline backend, bucketed dispatch,
            compile-ahead) over a 2,160-point qwen1.5-0.5b train grid;
            a fixed sample of records is checked against the per-point
            `simulate.predict` reference evaluated on the host CPU device
  frontier  the same grid with --frontier-only; its frontier must equal the
            Pareto filter of the full run's records
  traffic   a serving-traffic grid with feasible, capacity-infeasible and
            SLO-wall-failing rows, checked like the sweep
  train     three trainer steps of qwen1.5-0.5b at published widths
  serve     four requests through the server at published widths
  kernel    the Pallas GEMM compiled on the chip against `jnp.dot`

Four chips.  A pipeline sweep whose design groups fill 1,024 rows in one
superbatch runs pmap-sharded across the chips and is compared with the
serial backend on one chip; the trainer's first steps on the planner's
2x2 mesh are compared with the same steps on one chip.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU the script exits non-zero before any phase and prints no
such line.  Everything runs in this one process: the chip belongs to it.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import io
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# the reference runs on the host CPU device next to the chip, so the CPU
# backend must come up too when the platform list is pinned
_platforms = os.environ.get("JAX_PLATFORMS", "")
if _platforms and "cpu" not in _platforms.split(","):
    os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import devices, pathfind  # noqa: E402
from repro.core import (age, pathfinder, simulate, sweepexec,  # noqa: E402
                        sweeppipeline, sweeprunner, techlib)

OUT = os.path.join(ROOT, ".chip_smoke")

# Largest relative difference allowed between a sweep record computed on
# the chip and the per-point reference on the host CPU.  Both evaluate the
# same float32 program; a prediction chains a few hundred dependent
# operations, and the TPU's division and exp/log/pow differ from the CPU's
# by a few ulp (float32 ulp = 1.19e-7), so errors of a few hundred ulp,
# about 3e-5, can accumulate.  1e-4 leaves three times that.
REF_RTOL = 1e-4
N_SAMPLE = 32
# The Pallas GEMM and jnp.dot both accumulate bf16 products in float32 and
# round once to bf16; a different summation order may move an output by
# one bf16 ulp, 2^-7 of its magnitude.
GEMM_RTOL = 2.0 ** -7
# Losses of the same steps on a 2x2 mesh and on one chip: sharded matmuls
# sum in another order and the TPU multiplies float32 matrices in bf16
# passes, so activations differ by ~1e-3 relative; AdamW carries that into
# the next steps.  A sharding fault (a missing or doubled reduction) moves
# the loss by far more.
LOSS_RTOL = 1e-2

TRAIN_GRID = [
    "--arch", "qwen1.5-0.5b",
    "--mesh", "2x2", "--mesh", "2x4", "--mesh", "4x4", "--mesh", "4x8",
    "--mesh", "8x8", "--mesh", "8x16", "--mesh", "16x16",
    "--mesh", "2x16x16",
    "--logic", "N12,N7,N5,N3,N2,N1.5", "--hbm", "HBM2E,HBM3,HBM4",
    "--net", "IB-NDR-X8,IB-XDR-X8,IB-GDR-X8",
    "--scale", "0.8,0.9,1.0,1.1,1.2",
]
TRAFFIC_GRID = [
    "--scenario", "serving-traffic", "--arch", "qwen1.5-0.5b",
    "--mesh", "2x2", "--mesh", "4x4", "--mesh", "2x8",
    "--logic", "N7,N5", "--scale", "0.9,1.1", "--tilings", "4",
    "--chunk-size", "16",
    "--scenario-param", "qps=0.1",
    "--scenario-param", "prefill_chunk=1024,8192",
    "--scenario-param", "slo_ttft_p99=5,50",
]
# >= PMAP_MIN_ROWS hardware rows per (mesh, strategy) design, all in one
# superbatch, so every design group dispatches pmap-sharded
SHARDED_GRID = [
    "--arch", "qwen1.5-0.5b", "--mesh", "4x4", "--mesh", "8x8",
    "--logic", "N12,N7,N5,N3,N2,N1.5,N1", "--hbm", "HBM2,HBM2E,HBM3,HBM4",
    "--net", "IB-NDR-X8,IB-XDR-X8,IB-GDR-X8",
    "--scale", ",".join(f"{0.7 + 0.05 * i:.2f}" for i in range(13)),
]


def say(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------


def device_phase(chips: int) -> dict:
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu":
        sys.exit(f"chip_smoke: JAX finds no TPU (first device is "
                 f"{platform!r}); nothing was run")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
                 f"JAX finds {len(devs)}")
    info = {"platform": platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    say("device", **info)
    return info


# ---------------------------------------------------------------------------
# sweep records and their per-point reference
# ---------------------------------------------------------------------------


def run_cli(argv) -> dict:
    """`pathfind.main` in this process; the CSV it prints is dropped."""
    k0 = pathfinder.compile_cache_stats()
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(io.StringIO()):
        rc = pathfind.main(list(argv))
    seconds = time.perf_counter() - t0
    check(rc == 0, f"pathfind {' '.join(argv)} exited {rc}")
    k1 = pathfinder.compile_cache_stats()
    return {"seconds": round(seconds, 3),
            "compile_s": round(k1["compile_seconds"]
                               - k0["compile_seconds"], 3),
            "stall_s": round(k1["stall_seconds"] - k0["stall_seconds"], 3)}


def reference_record(spec, rec: dict) -> dict:
    """One record recomputed point by point, eagerly, on the host CPU."""
    lb = sweeprunner.label_from_record(rec)
    with jax.default_device(jax.devices("cpu")[0]):
        hw = age.generate(techlib.make_tech_config(lb.logic, lb.hbm, lb.net),
                          spec.budgets(lb.scale))
        dp = dataclasses.replace(sweeprunner.resolve_label(spec, lb), hw=hw)
        scn = sweeprunner.scenario_for(spec, lb.cell)
        ppe = sweeprunner.spec_ppe(spec)
        rows = []
        for ep in scn.eval_points(dp):
            bd = simulate.predict(ep.arch, ep.graph, ep.strategy,
                                  system=ep.system, cfg=ppe,
                                  pod_bw=ep.pod_bw)
            rows.append([float(bd.total_s), float(bd.compute_s),
                         float(bd.comm_s), float(bd.exposed_comm_s),
                         float(bd.pipeline_bubble_s)])
        return scn.record(dp, np.asarray(rows, dtype=np.float64))


def rel_diff(a, b) -> float:
    a, b = float(a), float(b)
    if a == b or (np.isnan(a) and np.isnan(b)):
        return 0.0
    if not (np.isfinite(a) and np.isfinite(b)):
        return float("inf")
    return abs(a - b) / max(abs(b), 1e-30)


def compare_records(got: dict, want: dict) -> float:
    """Largest relative difference over the shared numeric fields; flags
    (feasible, slo_ok) and labels must match exactly."""
    worst = 0.0
    for k, w in want.items():
        g = got.get(k)
        if isinstance(w, bool) or not isinstance(w, (int, float)):
            check(g == w, f"{got['key']}: {k} is {g!r}, reference {w!r}")
        elif g is not None:
            worst = max(worst, rel_diff(g, w))
    return worst


def check_sample(spec, records, n: int = N_SAMPLE) -> float:
    """Check an evenly spaced sample of the records, by key order."""
    ordered = sorted(records, key=lambda r: r["key"])
    step = max(len(ordered) // n, 1)
    sample = ordered[::step][:n]
    check(len(sample) >= min(n, len(ordered)), "reference sample too small")
    worst = max(compare_records(r, reference_record(spec, r))
                for r in sample)
    check(worst <= REF_RTOL, f"largest relative difference {worst:.3g} "
                             f"against the CPU reference exceeds {REF_RTOL}")
    return worst


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------


def sweep_phases() -> None:
    full_dir = os.path.join(OUT, "sweep")
    st = run_cli(["sweep", *TRAIN_GRID, "--out", full_dir])
    spec, records = sweeprunner.load_sweep(full_dir)
    designs = {(r["mesh"], r["strategy"]) for r in records}
    check(len(records) >= 2000 and len(designs) >= 8,
          f"{len(records)} points over {len(designs)} designs")
    worst = check_sample(spec, records)
    say("sweep", points=len(records), designs=len(designs), **st,
        sample=N_SAMPLE, max_rel_diff=f"{worst:.3g}", bound=REF_RTOL)

    front_dir = os.path.join(OUT, "frontier")
    st = run_cli(["sweep", *TRAIN_GRID, "--frontier-only", "--out",
                  front_dir])
    front = list(sweepexec.iter_jsonl(
        os.path.join(front_dir, "frontier.jsonl")))
    scn = spec.scenario_spec.variants()[0].resolve()
    want = sorted(r["key"] for r in sweeprunner.pareto_records(
        records, list(scn.objectives)))
    got = sorted(r["key"] for r in front)
    check(want and got == want, f"frontier-only keys {got} differ from the "
                                f"Pareto filter of the full run {want}")
    worst = max(compare_records(r, reference_record(spec, r))
                for r in front)
    check(worst <= REF_RTOL, f"frontier record off by {worst:.3g}")
    say("frontier", points=len(records), frontier=len(front), **st,
        matches_full=True, max_rel_diff=f"{worst:.3g}", bound=REF_RTOL)

    traffic_dir = os.path.join(OUT, "traffic")
    st = run_cli(["sweep", *TRAFFIC_GRID, "--out", traffic_dir])
    spec, records = sweeprunner.load_sweep(traffic_dir)
    regimes = {(bool(r["feasible"]), bool(r["slo_ok"])) for r in records}
    check((True, True) in regimes and (True, False) in regimes
          and (False, False) in regimes,
          f"serving-traffic regimes {sorted(regimes)}: need feasible, "
          f"SLO-wall-failing and capacity-infeasible rows")
    worst = check_sample(spec, records)
    say("traffic", points=len(records),
        feasible=sum(r["feasible"] and r["slo_ok"] for r in records),
        wall_failing=sum(r["feasible"] and not r["slo_ok"]
                         for r in records),
        infeasible=sum(not r["feasible"] for r in records), **st,
        sample=min(N_SAMPLE, len(records)), max_rel_diff=f"{worst:.3g}",
        bound=REF_RTOL)


# batch 4 x 256 tokens: float32 params, AdamW moments and gradients of the
# 0.46B-parameter model take ~7.4 GB, activations and logits ~3 GB more
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 4, 256, 3


def train_losses(mesh_shape) -> list:
    from repro.launch.train import TrainConfig, train
    out = train(TrainConfig(
        arch="qwen1.5-0.5b", use_reduced_config=False, mesh_shape=mesh_shape,
        steps=TRAIN_STEPS, global_batch=TRAIN_BATCH, seq_len=TRAIN_SEQ,
        warmup=1, log_every=TRAIN_STEPS + 1))
    losses = out["history"]
    check(len(losses) == TRAIN_STEPS and all(np.isfinite(losses)),
          f"losses {losses}")
    leaves = jax.tree.leaves(out["state"].params)
    platforms = {d.platform for x in leaves for d in x.devices()}
    check(platforms == {"tpu"}, f"parameters live on {platforms}")
    return losses


def train_phase() -> None:
    t0 = time.perf_counter()
    losses = train_losses((1, 1))
    say("train", arch="qwen1.5-0.5b", batch=TRAIN_BATCH, seq=TRAIN_SEQ,
        steps=TRAIN_STEPS, losses=[round(x, 4) for x in losses],
        params_on="tpu", seconds=round(time.perf_counter() - t0, 3))


def serve_phase() -> None:
    from repro.configs.base import get_config
    from repro.launch.serve import serve
    t0 = time.perf_counter()
    out = serve("qwen1.5-0.5b", batch=4, prompt_len=32, gen=16,
                use_reduced=False)
    tokens = out["tokens"]
    vocab = get_config("qwen1.5-0.5b").vocab_size
    check(tokens.shape == (4, 16), f"token shape {tokens.shape}")
    check(bool(((tokens >= 0) & (tokens < vocab)).all()),
          "generated tokens outside the vocabulary")
    say("serve", arch="qwen1.5-0.5b", requests=4, prompt=32, generated=16,
        tokens_in_vocab=True, seconds=round(time.perf_counter() - t0, 3))


def kernel_phase() -> None:
    from repro.kernels import common, ops
    check(not common.resolve_interpret(None),
          "Pallas kernels would run interpreted on this device")
    key = jax.random.PRNGKey(0)
    for m, k, n in ((2048, 1024, 2816), (1000, 1000, 1000)):
        kx, kw, key = jax.random.split(key, 3)
        x = jax.random.normal(kx, (m, k), jnp.float32).astype(jnp.bfloat16)
        w = jax.random.normal(kw, (k, n), jnp.float32).astype(jnp.bfloat16)
        hlo = ops.matmul.lower(x, w, use_pallas=True).as_text()
        check("tpu_custom_call" in hlo, "the GEMM did not lower to Mosaic")
        got = np.asarray(ops.matmul(x, w, use_pallas=True), np.float32)
        want = np.asarray(jnp.dot(x, w), np.float32)
        err = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
        check(err <= GEMM_RTOL, f"GEMM {m}x{k}x{n} off by {err:.3g}")
        say("kernel", gemm=f"{m}x{k}x{n}", dtype="bf16", compiled=True,
            max_rel_diff=f"{err:.3g}", bound=f"{GEMM_RTOL:.3g}")


# ---------------------------------------------------------------------------
# four-chip phases
# ---------------------------------------------------------------------------


def cold() -> None:
    """Forget every cached prediction and executable, so the next run
    evaluates each point itself."""
    pathfinder.clear_prediction_cache()
    pathfinder.clear_compiled_caches()


def sharded_sweep_phase(chips: int) -> None:
    cold()
    sharded_dir = os.path.join(OUT, "sharded")
    st = run_cli(["sweep", *SHARDED_GRID, "--superbatch", "4096",
                  "--out", sharded_dir])
    ran = sorted(k for k, e in pathfinder._COMPILED.items()
                 if isinstance(e, pathfinder.CompiledEntry) and e.aot
                 and k[-1] == chips)
    check(ran, f"no {chips}-device executable was built")
    spec, sharded = sweeprunner.load_sweep(sharded_dir)
    groups = {}
    for r in sharded:
        groups[(r["mesh"], r["strategy"])] = \
            groups.get((r["mesh"], r["strategy"]), 0) + 1
    check(min(groups.values()) >= sweeppipeline.PMAP_MIN_ROWS,
          f"design groups {groups} below {sweeppipeline.PMAP_MIN_ROWS} rows")
    say("sharded-sweep", points=len(sharded), groups=len(groups),
        rows_per_group=min(groups.values()), devices=chips,
        executables=[k[0] + "/" + str(k[-1]) for k in ran], **st)

    serial_dir = os.path.join(OUT, "serial")
    cold()
    st = run_cli(["sweep", *SHARDED_GRID, "--backend", "serial",
                  "--out", serial_dir])
    _, serial = sweeprunner.load_sweep(serial_dir)
    by_key = {r["key"]: r for r in serial}
    check(set(by_key) == {r["key"] for r in sharded},
          "sharded and one-chip runs scored different points")
    worst = max(compare_records(r, by_key[r["key"]]) for r in sharded)
    check(worst <= REF_RTOL, f"sharded records off by {worst:.3g}")
    say("one-chip-sweep", points=len(serial), backend="serial", **st,
        max_rel_diff=f"{worst:.3g}", bound=REF_RTOL)


def sharded_train_phase() -> None:
    mesh = train_losses((2, 2))
    one = train_losses((1, 1))
    worst = max(rel_diff(a, b) for a, b in zip(mesh, one))
    check(worst <= LOSS_RTOL, f"2x2 losses {mesh} vs one chip {one}")
    say("sharded-train", mesh="2x2", losses_2x2=[round(x, 5) for x in mesh],
        losses_1x1=[round(x, 5) for x in one],
        max_rel_diff=f"{worst:.3g}", bound=LOSS_RTOL)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the sharded paths and what they are "
                         "compared with, and nothing else")
    args = ap.parse_args(argv)
    info = device_phase(args.chips)
    devices.enable_compilation_cache()
    shutil.rmtree(OUT, ignore_errors=True)
    if args.chips == 1:
        sweep_phases()
        train_phase()
        serve_phase()
        kernel_phase()
    else:
        sharded_sweep_phase(args.chips)
        sharded_train_phase()
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
