"""DeepFlow pathfinding CLI — batched design-space exploration from a shell.

Subcommands:

  sweep   cross-product (arch x cell x mesh x logic x hbm x net) scored by
          the batched evaluator; prints CSV (optionally only the Pareto
          frontier) and can write it to a file:

              PYTHONPATH=src python -m repro.pathfind sweep \
                  --arch qwen1.5-0.5b --cell train_4k \
                  --mesh 8x8 --mesh 16x16 \
                  --logic N7,N5,N3 --hbm HBM2E,HBM3 --csv sweep.csv

          With --out DIR the sweep runs on the chunked, resumable engine
          (repro.core.sweeprunner; default backend = the pipelined
          executor of repro.core.sweeppipeline): results stream to
          DIR/results.jsonl, finished chunks are checkpointed, and an
          interrupted sweep continues with ZERO re-evaluation via:

              PYTHONPATH=src python -m repro.pathfind sweep \
                  --out sweeps/serve --resume

          --scenario picks the workload semantics (scenario registry,
          repro.core.scenarios): "train" = step time; "serving" =
          prefill+decode TTFT / tokens-per-sec-per-device with KV-cache
          memory pressure; "serving-long" = 500k-token decode (recurrent /
          hybrid archs).  --arch all sweeps every registered config:

              PYTHONPATH=src python -m repro.pathfind sweep \
                  --scenario serving --arch all --mesh 16x16 \
                  --logic N7,N5 --slo 10 --out sweeps/serve

          --frontier-only streams every point through a device-resident
          Pareto reduction fused into the compiled evaluator: only the
          frontier is materialized/printed (DIR/frontier.jsonl), so
          10^6-point sweeps never pull per-point rows to host.  The
          carried state checkpoints to DIR/frontier_state.npz per
          committed superbatch, so --resume continues an interrupted
          frontier sweep with zero re-evaluation.

          --scenario serving-traffic scores continuous batching with
          chunked prefill under a QPS arrival model (repro.core.traffic):
          TTFT/TPOT percentiles, utilization walls, and device-seconds
          per token.  Traffic/batching params are typed --scenario-param
          flags; a comma list (e.g. --scenario-param
          prefill_chunk=256,512) declares a sweep axis.

  explore surrogate-driven exploration (repro.core.surrogate): instead of
          exhausting the cross-product, fit an ensemble of small jit'd
          MLPs (mean + epistemic spread + feasibility head) on the points
          evaluated so far and spend the real-evaluation budget on the
          top-acquisition chunks (UCB / expected-Pareto-improvement over
          the canonical-signed objectives) until the frontier stagnates
          or the budget runs out.  The output directory is a normal
          partial sweep (spec.json / results.jsonl / checkpoint.jsonl) —
          resumable, and readable by size/cooptimize:

              PYTHONPATH=src python -m repro.pathfind explore \
                  --arch qwen1.5-0.5b --mesh 2x2 --mesh 4x4 \
                  --logic N7,N5 --scale 0.9,1.1 \
                  --eval-frac 0.25 --out sweeps/explore

          With --order-dir DIR the surrogate instead ranks a fabric
          sweep directory's chunks and writes DIR/order.json — an
          advisory claim order that makes `sweep --workers N` fleets
          evaluate frontier-adjacent chunks first (results are
          byte-identical to an unordered run; only the schedule moves).

  size    inverse fleet sizing over a swept design space: the minimum
          device count serving --qps under percentile SLO walls, by
          doubling+bisection on the closed-form traffic model — swept
          points are never re-evaluated:

              PYTHONPATH=src python -m repro.pathfind size \
                  --from sweeps/traffic --qps 24 \
                  --slo-ttft-p99 2.0 --slo-tpot-p50 0.05

          --rank-by cost_per_token | energy_per_token re-ranks the
          feasible fleet plans by the PR8 objective columns already in
          the swept records (zero re-evaluation; needs a sweep run with
          --objectives energy,cost)

  plan    the CrossFlow -> runtime bridge: best runtime-realizable strategy
          for one (arch, cell, mesh) on the TPU-v5e micro-arch:

              PYTHONPATH=src python -m repro.pathfind plan \
                  --arch qwen1.5-0.5b --cell train_4k --mesh 16x16

  soe     joint strategy x hardware-budget co-optimization (paper §7/§9.2)
          with the batched multi-start GD:

              PYTHONPATH=src python -m repro.pathfind soe \
                  --arch qwen1.5-0.5b --cell train_4k --devices 64 \
                  --steps 10 --starts 4

  calibrate  measurement-driven calibration (repro.calibrate): run the
          microbenchmark suite on THIS machine (jit'd GEMMs, optionally
          Pallas kernels, forced-multi-device collectives, model-family
          steps), fit the techlib/PPE efficiency+overhead vector to the
          measurements by multi-start GD through the traced performance
          model, and write DIR/profile.json + DIR/report.json (the drift
          baseline).  Resumable like a sweep (--resume skips measured
          points):

              PYTHONPATH=src python -m repro.pathfind calibrate \
                  --out calib --suite quick

  validate  re-measure (or reuse) the suite and diff the validation
          report against the stored baseline — non-zero exit on drift:

              PYTHONPATH=src python -m repro.pathfind validate --out calib

  cooptimize  cross-stack sweep -> refine: load a checkpointed sweep's
          Pareto frontier and run batched gradient refinement around each
          frontier point, jointly over continuous technology knobs (DVFS
          voltage, HBM bandwidth/capacity scaling), the hardware budget
          vector (eq.-6 SOE update), and the discrete strategy/mesh axis
          (ranked from the sweep's own records — scored points are never
          re-evaluated).  Refined records stream to DIR/refined.jsonl in
          the sweep's JSONL schema:

              PYTHONPATH=src python -m repro.pathfind cooptimize \
                  --from sweeps/serve --top-k 4 --steps 24
"""

from __future__ import annotations

import argparse
import sys
from typing import List, Tuple

from repro import devices


def _mesh(text: str) -> Tuple[int, ...]:
    try:
        dims = tuple(int(x) for x in text.lower().split("x"))
    except ValueError:
        dims = ()
    if not dims or any(d <= 0 for d in dims):
        raise argparse.ArgumentTypeError(
            f"bad mesh {text!r}; expected e.g. 16x16 or 2x16x16")
    return dims


def _csv_list(text: str) -> List[str]:
    return [x.strip() for x in text.split(",") if x.strip()]


def _scenario_param(text: str) -> Tuple[str, object]:
    """KEY=V or KEY=V1,V2,... (a comma list declares a sweep axis)."""
    key, sep, val = text.partition("=")
    vals = [v for v in val.split(",") if v]
    if not sep or not key or not vals:
        raise argparse.ArgumentTypeError(
            f"bad scenario param {text!r}; expected KEY=V or KEY=V1,V2,...")
    try:
        out = [float(v) for v in vals]
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"bad scenario param {text!r}: values must be numbers")
    return key.strip(), out[0] if len(out) == 1 else out


def _scenario_params_dict(pairs) -> dict:
    return dict(pairs or ())


# -- shared flag groups (sweep / cooptimize / size) -------------------------
# one scenario/profile/out-dir vocabulary across subcommands: a flag means
# the same thing everywhere, and commands that read their spec from a
# directory refuse contradicting flags instead of silently ignoring them


def _add_axis_flags(p) -> None:
    g = p.add_argument_group("design-space axes")
    g.add_argument("--arch", action="append", default=None,
                   help="model arch id (repeatable; 'all' = every config)")
    g.add_argument("--cell", action="append", default=None,
                   help="shape cell name (repeatable; default from the "
                        "scenario, e.g. train_4k / prefill_32k+decode_32k)")
    g.add_argument("--mesh", action="append", type=_mesh, default=None,
                   help="mesh shape like 16x16 (repeatable)")
    g.add_argument("--logic", type=_csv_list, default=["N7"],
                   help="comma-separated logic nodes (default N7)")
    g.add_argument("--hbm", type=_csv_list, default=["HBM2E"],
                   help="comma-separated HBM generations")
    g.add_argument("--net", type=_csv_list, default=["IB-NDR-X8"],
                   help="comma-separated inter-node networks")
    g.add_argument("--area", type=float, default=None,
                   help="proc chip area budget (mm^2)")
    g.add_argument("--power", type=float, default=None,
                   help="node power budget (W)")
    g.add_argument("--scale", type=_csv_list, default=None,
                   metavar="S1,S2,...",
                   help="budget-scale variants (e.g. 0.8,1.0,1.2) "
                        "multiplying area+power per hardware point")
    g.add_argument("--tilings", type=int, default=8,
                   help="PPE tiling samples per level")


def _add_scenario_flags(p, default_scenario: str = "train") -> None:
    g = p.add_argument_group("scenario")
    g.add_argument("--scenario", default=default_scenario,
                   help="workload scenario: train | serving | serving-long "
                        "| serving-traffic (continuous batching + "
                        "percentile SLO walls)")
    g.add_argument("--slo", type=float, default=None,
                   help="serving TTFT SLO in seconds (tags slo_ok; for "
                        "serving-traffic this is the p99 TTFT wall)")
    g.add_argument("--scenario-param", action="append",
                   type=_scenario_param, default=None,
                   metavar="KEY=V[,V2,...]",
                   help="typed scenario parameter (repeatable); for "
                        "serving-traffic: qps, prompt_mean, prompt_cv, "
                        "output_mean, output_cv, prefill_chunk, "
                        "slo_ttft_p50/p99, slo_tpot_p50/p99.  A comma "
                        "list declares a sweep axis (variants ride in "
                        "the cell id)")
    g.add_argument("--objectives", type=_csv_list, default=None,
                   metavar="OBJ1,OBJ2,...",
                   help="Pareto objectives from the objective registry "
                        "(repro.core.objectives): 'energy', 'cost', "
                        "'goodput' (kind-matched aliases), canonical "
                        "names like energy_j_per_token, or the "
                        "scenario's own record fields.  Replaces the "
                        "scenario's default objective set everywhere — "
                        "frontier folds, --frontier-only streaming "
                        "Pareto, cooptimize refinement")
    g.add_argument("--profile", default=None, metavar="FILE",
                   help="calibration profile JSON (pathfind calibrate); "
                        "every hardware point is evaluated on the "
                        "measurement-anchored MicroArch")


def _parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="repro.pathfind", description=__doc__,
                                formatter_class=argparse.RawDescriptionHelpFormatter)
    sub = p.add_subparsers(dest="cmd", required=True)

    sw = sub.add_parser("sweep", help="batched design-space sweep")
    _add_axis_flags(sw)
    _add_scenario_flags(sw)
    sw.add_argument("--pareto", type=_csv_list, default=None, metavar="OBJS",
                    help="print only the Pareto frontier over these "
                         "objectives (default: the scenario's, e.g. "
                         "time_s,devices)")
    sw.add_argument("--csv", default=None, help="also write CSV here")
    # sharded resumable engine (repro.core.sweeprunner)
    sw.add_argument("--out", default=None,
                    help="stream results + checkpoints into this directory "
                         "(enables --resume)")
    sw.add_argument("--resume", action="store_true",
                    help="continue an interrupted sweep from --out "
                         "(spec loaded from DIR/spec.json; zero "
                         "re-evaluation of finished chunks)")
    sw.add_argument("--chunk-size", type=int, default=32,
                    help="design points per chunk (checkpoint granularity)")
    sw.add_argument("--workers", type=int, default=None,
                    help="parallel chunk workers: on the pipeline/auto "
                         "backend this spawns N `sweep-worker` processes "
                         "over --out DIR (the distributed sweep fabric; "
                         "0 = initialize the directory and wait for an "
                         "external fleet); on thread/process backends it "
                         "is the pool size")
    sw.add_argument("--lease-ttl", type=float, default=None,
                    help="fabric chunk-lease TTL in seconds (default 30; "
                         "workers heartbeat at ttl/3, expired leases are "
                         "reclaimed — set comfortably above one "
                         "superbatch's evaluation time)")
    sw.add_argument("--backend", default="auto",
                    choices=["auto", "pipeline", "serial", "thread",
                             "process", "device"],
                    help="chunk fan-out: auto = the pipelined executor "
                         "(async double-buffered producer/device/writer "
                         "pipeline, device-sharded when >1 JAX device)")
    sw.add_argument("--max-chunks", type=int, default=None,
                    help="stop after N chunks (testing/benchmarks; "
                         "combine with --resume to continue)")
    sw.add_argument("--superbatch", type=int, default=None,
                    help="design points per device dispatch on the "
                         "pipeline backend (default 256; commit "
                         "granularity stays --chunk-size)")
    sw.add_argument("--frontier-only", action="store_true",
                    help="device-resident streaming-Pareto mode: only "
                         "the frontier over the scenario's objectives is "
                         "materialized/printed (DIR/frontier.jsonl with "
                         "--out); per-point rows never reach the host; "
                         "the carried state checkpoints to "
                         "DIR/frontier_state.npz per committed superbatch "
                         "(--resume continues with zero re-evaluation)")
    sw.add_argument("--frontier-cap", type=int, default=None,
                    help="carried device frontier capacity (default 512; "
                         "overflow is reported, never silent)")
    sw.add_argument("--compile-ahead", type=int, default=None,
                    metavar="N",
                    help="superbatches to pack and AOT-compile ahead of "
                         "the device stage on the pipeline backend "
                         "(default 2; the compile service builds "
                         "executables off the critical path so the "
                         "device stage only dispatches warm functions)")
    sw.add_argument("--no-bucketing", action="store_true",
                    help="disable cross-design bucketed dispatch (compile "
                         "one function per design group instead of one "
                         "per shape bucket; execution-only — results are "
                         "numerically equivalent)")

    wk = sub.add_parser(
        "sweep-worker",
        help="join a fabric sweep directory as a lease-claiming worker")
    wk.add_argument("--dir", required=True,
                    help="fabric sweep directory (initialized by `sweep "
                         "--workers N --out DIR`); mode and spec are read "
                         "from the directory, so a fleet cannot disagree")
    wk.add_argument("--id", default=None,
                    help="worker id (default: unique per process "
                         "incarnation — keep the default unless you know "
                         "why)")
    wk.add_argument("--ttl", type=float, default=None,
                    help="lease TTL seconds (default 30)")
    wk.add_argument("--poll", type=float, default=None,
                    help="idle/coordination poll interval seconds "
                         "(default 0.5)")
    wk.add_argument("--claim-batch", type=int, default=None,
                    help="chunks to lease per claim round (default: one "
                         "superbatch's worth)")
    wk.add_argument("--superbatch", type=int, default=None,
                    help="design points per device dispatch (default 256)")
    wk.add_argument("--compile-ahead", type=int, default=None, metavar="N",
                    help="superbatches to pack and AOT-compile ahead of "
                         "the device stage (default 2)")
    wk.add_argument("--no-bucketing", action="store_true",
                    help="disable cross-design bucketed dispatch")
    wk.add_argument("--eval-delay", type=float, default=0.0,
                    help="artificial per-chunk device latency in seconds "
                         "(fan-out benchmarks / fault tests)")
    wk.add_argument("--max-chunks", type=int, default=None,
                    help="exit after committing N chunks (testing)")

    pl = sub.add_parser("plan", help="runtime sharding plan for one point")
    pl.add_argument("--arch", required=True)
    pl.add_argument("--cell", required=True)
    pl.add_argument("--mesh", type=_mesh, required=True)

    co = sub.add_parser("cooptimize",
                        help="sweep -> refine cross-stack co-optimization")
    co.add_argument("--from", dest="from_dir", required=True, metavar="DIR",
                    help="checkpointed sweep directory (spec.json + "
                         "results.jsonl); seeds are read, never re-scored")
    co.add_argument("--scenario", default=None,
                    help="must match the sweep's scenario if given "
                         "(the spec in DIR is authoritative)")
    co.add_argument("--top-k", type=int, default=4,
                    help="frontier points to refine (default 4)")
    co.add_argument("--candidates", type=int, default=2,
                    help="discrete (mesh, strategy) candidates per seed, "
                         "ranked from the sweep's own records (default 2)")
    co.add_argument("--steps", type=int, default=24,
                    help="refinement GD steps (default 24)")
    co.add_argument("--starts", type=int, default=4,
                    help="multi-start batch size (default 4)")
    co.add_argument("--lr", type=float, default=0.05)
    co.add_argument("--seed", type=int, default=0)
    co.add_argument("--scenario-param", action="append",
                    type=_scenario_param, default=None,
                    metavar="KEY=V[,V2,...]",
                    help="must match the sweep's scenario params if given "
                         "(the spec in DIR is authoritative)")
    co.add_argument("--objectives", type=_csv_list, default=None,
                    metavar="OBJ1,OBJ2,...",
                    help="must match the sweep's objectives if given "
                         "(the spec in DIR is authoritative)")
    co.add_argument("--out", default=None, metavar="FILE",
                    help="refined-records JSONL path "
                         "(default DIR/refined.jsonl)")
    co.add_argument("--csv", default=None, help="also write CSV here")

    ex = sub.add_parser("explore",
                        help="surrogate-driven exploration: spend a "
                             "real-evaluation budget on top-acquisition "
                             "chunks instead of the full cross-product")
    _add_axis_flags(ex)
    _add_scenario_flags(ex)
    ex.add_argument("--out", default=None,
                    help="stream evaluated chunks + checkpoints into this "
                         "directory (a normal partial sweep; enables "
                         "--resume)")
    ex.add_argument("--resume", action="store_true",
                    help="continue from --out (spec loaded from "
                         "DIR/spec.json; committed chunks are never "
                         "re-evaluated and keep training the surrogate)")
    ex.add_argument("--chunk-size", type=int, default=8,
                    help="design points per evaluated chunk (default 8; "
                         "acquisition ranks whole chunks)")
    ex.add_argument("--train-from", default=None, metavar="DIR",
                    help="seed the surrogate with a finished/partial "
                         "sweep directory's records (read via the "
                         "torn-line-tolerant JSONL reader; they count "
                         "toward the training floor, not the budget)")
    ex.add_argument("--eval-budget", type=int, default=None,
                    help="hard ceiling on real-evaluated points "
                         "(default: --eval-frac of the grid)")
    ex.add_argument("--eval-frac", type=float, default=0.25,
                    help="budget as a fraction of the full grid when "
                         "--eval-budget is not given (default 0.25)")
    ex.add_argument("--init-chunks", type=int, default=4,
                    help="evenly-spread seed chunks before the first fit "
                         "(default 4)")
    ex.add_argument("--batch-chunks", type=int, default=4,
                    help="top-acquisition chunks evaluated per round "
                         "(default 4)")
    ex.add_argument("--stagnation", type=int, default=3,
                    help="stop after N rounds with an unchanged frontier "
                         "(default 3)")
    ex.add_argument("--acquisition", default="ucb",
                    choices=["ucb", "epi"],
                    help="chunk-ranking rule: ucb = optimistic dominance "
                         "margin; epi = expected Pareto improvement")
    ex.add_argument("--kappa", type=float, default=1.0,
                    help="UCB exploration weight (default 1.0)")
    ex.add_argument("--ensemble", type=int, default=4,
                    help="surrogate ensemble size (default 4)")
    ex.add_argument("--hidden", type=int, default=32,
                    help="surrogate hidden width (default 32)")
    ex.add_argument("--steps", type=int, default=300,
                    help="surrogate fit steps per round (default 300)")
    ex.add_argument("--lr", type=float, default=0.01)
    ex.add_argument("--seed", type=int, default=0)
    ex.add_argument("--csv", default=None,
                    help="also write the explored frontier CSV here")
    ex.add_argument("--order-dir", default=None, metavar="DIR",
                    help="rank DIR's fabric chunks with the surrogate "
                         "and write DIR/order.json (advisory worker "
                         "claim order) instead of evaluating anything; "
                         "trains on DIR's committed shards plus "
                         "--train-from")

    sz = sub.add_parser("size",
                        help="inverse fleet sizing: minimum device count "
                             "serving --qps under percentile SLO walls")
    sz.add_argument("--from", dest="from_dir", default=None, metavar="DIR",
                    help="checkpointed serving-traffic sweep directory; "
                         "swept points are read, never re-scored.  "
                         "Without --from, the design-space axes below "
                         "run a fresh in-memory sweep first")
    _add_axis_flags(sz)
    _add_scenario_flags(sz, default_scenario="serving-traffic")
    sz.add_argument("--qps", type=float, required=True,
                    help="offered load (requests/s) to serve")
    sz.add_argument("--slo-ttft-p50", type=float, default=None,
                    help="median TTFT wall in seconds")
    sz.add_argument("--slo-ttft-p99", type=float, default=None,
                    help="p99 TTFT wall in seconds")
    sz.add_argument("--slo-tpot-p50", type=float, default=None,
                    help="median TPOT wall in seconds")
    sz.add_argument("--slo-tpot-p99", type=float, default=None,
                    help="p99 TPOT wall in seconds")
    sz.add_argument("--top-k", type=int, default=5,
                    help="feasible designs to report (default 5)")
    sz.add_argument("--rank-by", default="devices",
                    choices=["devices", "cost_per_token",
                             "energy_per_token"],
                    help="fleet-plan ranking: devices (default) or a "
                         "PR8 objective column already in the swept "
                         "records ($/token, J/token) — zero "
                         "re-evaluation")
    sz.add_argument("--out", default=None,
                    help="stream the fresh sweep's results + checkpoints "
                         "into this directory (axes mode only)")
    sz.add_argument("--chunk-size", type=int, default=32,
                    help="design points per chunk (axes mode)")
    sz.add_argument("--backend", default="auto",
                    choices=["auto", "pipeline", "serial", "thread",
                             "process", "device"],
                    help="sweep backend (axes mode)")

    ca = sub.add_parser("calibrate",
                        help="measure this machine and fit a calibration "
                             "profile")
    ca.add_argument("--out", required=True, metavar="DIR",
                    help="measurement + profile output directory")
    ca.add_argument("--suite", default="quick", choices=["quick", "full"],
                    help="microbenchmark suite (quick = GEMM-only)")
    ca.add_argument("--reps", type=int, default=3,
                    help="timing repetitions per point (best-of)")
    ca.add_argument("--resume", action="store_true",
                    help="skip points already in DIR/measurements.jsonl")
    ca.add_argument("--tech", default="cpu_host", choices=["cpu_host",
                                                           "tpu_v5e"],
                    help="techlib entry the profile anchors")
    ca.add_argument("--steps", type=int, default=80,
                    help="fit GD steps (default 80)")
    ca.add_argument("--starts", type=int, default=6,
                    help="fit multi-start batch (default 6)")
    ca.add_argument("--tilings", type=int, default=8,
                    help="PPE tiling samples during fit/validation")
    ca.add_argument("--seed", type=int, default=0)

    va = sub.add_parser("validate",
                        help="validation report + drift vs stored baseline")
    va.add_argument("--out", required=True, metavar="DIR",
                    help="calibration directory (measurements + profile)")
    va.add_argument("--profile", default=None, metavar="FILE",
                    help="profile JSON (default DIR/profile.json)")
    va.add_argument("--baseline", default=None, metavar="FILE",
                    help="stored baseline report (default DIR/report.json)")
    va.add_argument("--remeasure", action="store_true",
                    help="re-run the microbenchmark suite instead of "
                         "reusing DIR/measurements.jsonl")
    va.add_argument("--update-baseline", action="store_true",
                    help="overwrite the baseline with this report")
    va.add_argument("--drift-tol", type=float, default=0.25,
                    help="allowed absolute MRE worsening per group "
                         "(default 0.25 = 25 points)")
    va.add_argument("--tilings", type=int, default=None,
                    help="PPE tiling samples (default: the profile's "
                         "fit-time value, so the drift gate compares "
                         "like with like)")

    so = sub.add_parser("soe", help="strategy x budget co-optimization")
    so.add_argument("--arch", required=True)
    so.add_argument("--cell", required=True)
    so.add_argument("--devices", type=int, default=64)
    so.add_argument("--logic", default="N7")
    so.add_argument("--hbm", default="HBM2E")
    so.add_argument("--net", default="IB-NDR-X8")
    so.add_argument("--steps", type=int, default=20)
    so.add_argument("--starts", type=int, default=4)
    so.add_argument("--tilings", type=int, default=8)
    so.add_argument("--no-search-arch", action="store_true",
                    help="rank strategies only (skip the budget GD)")
    return p


def _cmd_sweep(args) -> int:
    # every flag the chunked engine owns must route there — a runner-only
    # flag silently dropped by the in-memory path is a footgun
    use_runner = bool(args.out or args.resume or args.scenario != "train"
                      or args.scale or args.max_chunks is not None
                      or args.backend != "auto" or args.slo is not None
                      or args.workers is not None or args.chunk_size != 32
                      or args.profile is not None
                      or args.scenario_param or args.objectives
                      or args.frontier_only or args.superbatch is not None
                      or args.frontier_cap is not None
                      or args.lease_ttl is not None
                      or args.compile_ahead is not None
                      or args.no_bucketing
                      or (args.arch and "all" in args.arch))
    if use_runner:
        return _cmd_sweep_runner(args)

    import dataclasses
    from repro.core import pathfinder
    from repro.core.age import Budgets
    from repro.core.roofline import PPEConfig

    if not (args.arch and args.mesh):
        print("error: sweep needs --arch and --mesh (or --resume with "
              "--out)", file=sys.stderr)
        return 2
    cells = args.cell or ["train_4k"]
    budgets = Budgets.default()
    if args.area is not None:
        budgets = dataclasses.replace(budgets, proc_chip_area_mm2=args.area)
    if args.power is not None:
        budgets = dataclasses.replace(budgets, power_w=args.power)
    result = pathfinder.sweep(
        args.arch, cells, args.mesh, logic_nodes=args.logic,
        hbms=args.hbm, nets=args.net, budgets=budgets,
        ppe=PPEConfig(n_tilings=args.tilings))
    points = result.points
    if args.pareto:
        points = result.pareto(objectives=args.pareto)
    lines = [pathfinder.CSV_HEADER] + [p.as_csv_row() for p in points]
    print("\n".join(lines))
    if args.csv:
        with open(args.csv, "w") as fh:
            fh.write("\n".join(lines) + "\n")
        print(f"# wrote {len(points)} points to {args.csv}", file=sys.stderr)
    best = result.best()
    print(f"# best: {best.arch}/{best.cell} mesh="
          f"{'x'.join(map(str, best.mesh))} {best.logic}/{best.hbm}/"
          f"{best.net} {best.strategy.name} -> {best.time_s*1e3:.2f} ms",
          file=sys.stderr)
    return 0


def _validate_dispatch_args(args) -> int:
    """Reject nonsensical dispatch sizing up front (rc 2) instead of
    letting a `--superbatch 0` surface as a reshape traceback mid-sweep."""
    superbatch = getattr(args, "superbatch", None)
    if superbatch is not None and superbatch <= 0:
        print(f"error: --superbatch must be a positive number of design "
              f"points (got {superbatch}); drop the flag for the default "
              f"(256)", file=sys.stderr)
        return 2
    compile_ahead = getattr(args, "compile_ahead", None)
    if compile_ahead is not None and compile_ahead <= 0:
        print(f"error: --compile-ahead must be a positive number of "
              f"superbatches to pre-compile (got {compile_ahead}); drop "
              f"the flag for the default (2), or use --no-bucketing to "
              f"fall back to per-group lazy compilation", file=sys.stderr)
        return 2
    return 0


def _runner_exec_kwargs(args) -> dict:
    """Execution-only knobs shared by sweep and sweep-worker — no effect
    on spec fingerprints, chunk hashes, or resume."""
    return dict(
        compile_ahead=args.compile_ahead,
        bucketing=False if args.no_bucketing else None)


def _cmd_sweep_runner(args) -> int:
    """Sharded / chunked / resumable path (repro.core.sweeprunner)."""
    from repro.core import scenarios, sweeprunner

    rc = _validate_dispatch_args(args)
    if rc:
        return rc
    kwargs = dict(backend=args.backend, workers=args.workers,
                  superbatch=args.superbatch, **_runner_exec_kwargs(args))
    if args.frontier_only:
        if args.pareto:
            print("error: --frontier-only already reduces to the "
                  "scenario's Pareto objectives on device; drop --pareto",
                  file=sys.stderr)
            return 2
    if args.resume:
        if not args.out:
            print("error: --resume requires --out DIR", file=sys.stderr)
            return 2
        # the spec comes from DIR/spec.json; axis/scenario flags on the
        # command line would be silently contradicted, so refuse them
        ignored = [name for name, val, default in (
            ("--arch", args.arch, None), ("--cell", args.cell, None),
            ("--mesh", args.mesh, None), ("--logic", args.logic, ["N7"]),
            ("--hbm", args.hbm, ["HBM2E"]),
            ("--net", args.net, ["IB-NDR-X8"]),
            ("--scale", args.scale, None), ("--area", args.area, None),
            ("--power", args.power, None), ("--slo", args.slo, None),
            ("--scenario", args.scenario, "train"),
            ("--chunk-size", args.chunk_size, 32),
            ("--tilings", args.tilings, 8),
            ("--profile", args.profile, None),
            ("--scenario-param", args.scenario_param, None),
            ("--objectives", args.objectives, None),
        ) if val != default]
        if ignored:
            print(f"error: --resume loads the sweep spec from "
                  f"{args.out}/spec.json; drop these flags (they would "
                  f"be ignored): {', '.join(ignored)}", file=sys.stderr)
            return 2
        runner = sweeprunner.SweepRunner.from_dir(args.out, **kwargs)
    else:
        if not (args.arch and args.mesh):
            print("error: sweep needs --arch and --mesh (or --resume with "
                  "--out)", file=sys.stderr)
            return 2
        profile_dict = None
        if args.profile is not None:
            from repro.calibrate import profiles as profiles_lib
            profile_dict = profiles_lib.load_profile(args.profile).to_dict()
            print(f"# profile: {args.profile} "
                  f"(tech={profile_dict.get('tech')})", file=sys.stderr)
        spec = sweeprunner.SweepSpec(
            arches=tuple(args.arch),
            mesh_shapes=tuple(tuple(m) for m in args.mesh),
            scenario=args.scenario, cells=tuple(args.cell or ()),
            logic_nodes=tuple(args.logic), hbms=tuple(args.hbm),
            nets=tuple(args.net),
            budget_scales=tuple(float(s) for s in args.scale) if args.scale
            else (1.0,),
            area_mm2=args.area, power_w=args.power, slo_s=args.slo,
            n_tilings=args.tilings, chunk_size=args.chunk_size,
            profile=profile_dict,
            scenario_params=_scenario_params_dict(args.scenario_param)
            or None,
            objectives=tuple(args.objectives) if args.objectives else None)
        runner = sweeprunner.SweepRunner(spec, out_dir=args.out, **kwargs)

    # --workers on the pipeline backend = the distributed sweep fabric:
    # spawn N sweep-worker processes over --out and merge their shards
    if args.workers is not None and runner.backend == "pipeline":
        return _cmd_sweep_fabric(args, runner.spec)
    if args.lease_ttl is not None:
        print("error: --lease-ttl is a fabric knob; combine it with "
              "--workers N on the pipeline/auto backend", file=sys.stderr)
        return 2

    run_kwargs = dict(resume=args.resume, max_chunks=args.max_chunks,
                      frontier_only=args.frontier_only)
    if args.frontier_cap is not None:
        run_kwargs["frontier_capacity"] = args.frontier_cap
    stats = runner.run(**run_kwargs)
    # any variant resolves the same fields/objectives for CSV + frontier
    scn = runner.spec.scenario_spec.variants()[0].resolve()
    records = stats.records or []
    shown = records
    objectives = args.pareto or list(scn.objectives)
    if args.pareto:
        shown = sweeprunner.pareto_records(records, objectives)
    csv_text = sweeprunner.to_csv(shown, scn)
    print(csv_text)
    if args.csv:
        with open(args.csv, "w") as fh:
            fh.write(csv_text + "\n")
        print(f"# wrote {len(shown)} points to {args.csv}", file=sys.stderr)
    mode = " frontier-only" if stats.frontier_only else ""
    print(f"# sweep[{scn.name}]{mode} backend={stats.backend}: "
          f"{stats.n_points_total} points in {stats.n_chunks_total} chunks; "
          f"skipped {stats.n_chunks_skipped} checkpointed, evaluated "
          f"{stats.n_chunks_evaluated} "
          f"({stats.n_points_evaluated} points) in {stats.elapsed_s:.1f}s",
          file=sys.stderr)
    print(f"# cache: prediction {stats.cache_hits} hits / "
          f"{stats.cache_misses} misses; compiled fns "
          f"{stats.compile_misses} built / {stats.compile_hits} reused",
          file=sys.stderr)
    print(f"# compile: {stats.compile_seconds:.1f}s building XLA "
          f"executables, {stats.stall_seconds:.1f}s stalling the eval "
          f"path (compile-ahead hides the rest)", file=sys.stderr)
    if stats.frontier_only:
        print(f"# frontier: {len(records)} non-dominated points over "
              f"{'/'.join(scn.objectives)}", file=sys.stderr)
        if stats.n_frontier_overflowed:
            print(f"# warning: device frontier capacity overflowed "
                  f"({stats.n_frontier_overflowed} candidates dropped); "
                  f"raise --frontier-cap", file=sys.stderr)
    if not stats.complete:
        if stats.frontier_only and stats.out_dir:
            print(f"# incomplete: resume with `python -m repro.pathfind "
                  f"sweep --out {stats.out_dir} --resume --frontier-only`"
                  f" (carried state in frontier_state.npz)",
                  file=sys.stderr)
        elif stats.frontier_only:
            print("# incomplete (no --out directory: the carried frontier "
                  "state was not checkpointed)", file=sys.stderr)
        elif stats.out_dir:
            print(f"# incomplete: resume with `python -m repro.pathfind "
                  f"sweep --out {stats.out_dir} --resume`", file=sys.stderr)
        else:
            print("# incomplete (no --out directory: nothing was "
                  "checkpointed)", file=sys.stderr)
    feasible = [r for r in records
                if r.get("feasible", True)
                and r.get(objectives[0]) is not None
                and float(r[objectives[0]]) > 0.0]
    if feasible:
        best = min(feasible, key=lambda r: float(r[objectives[0]]))
        print(f"# best[{objectives[0]}]: {best['key']} -> "
              f"{float(best[objectives[0]]):.4g}", file=sys.stderr)
    return 0


def _cmd_sweep_fabric(args, spec) -> int:
    """Distributed fabric path of `sweep`: coordinator + N local workers
    (repro.core.sweepfabric)."""
    from repro.core import sweepfabric, sweeprunner

    if not args.out:
        print("error: --workers N on the pipeline backend is the "
              "distributed sweep fabric; it needs --out DIR (the shared "
              "coordination directory)", file=sys.stderr)
        return 2
    if args.max_chunks is not None:
        print("error: --max-chunks is incompatible with the fabric (the "
              "coordinator waits for global completion); use "
              "`sweep-worker --max-chunks` on an individual worker",
              file=sys.stderr)
        return 2
    coord = sweepfabric.FabricCoordinator(
        spec, args.out, workers=args.workers,
        ttl_s=args.lease_ttl or sweepfabric.DEFAULT_TTL_S,
        frontier_only=args.frontier_only,
        frontier_capacity=args.frontier_cap,
        superbatch=args.superbatch,
        compile_ahead=args.compile_ahead,
        bucketing=False if args.no_bucketing else None)
    if args.workers == 0:
        print(f"# fabric: directory initialized; join workers with "
              f"`python -m repro.pathfind sweep-worker --dir {args.out}`",
              file=sys.stderr)
    stats = coord.run()
    scn = spec.scenario_spec.variants()[0].resolve()
    records = stats.records or []
    shown = records
    objectives = args.pareto or list(scn.objectives)
    if args.pareto:
        shown = sweeprunner.pareto_records(records, objectives)
    csv_text = sweeprunner.to_csv(shown, scn)
    print(csv_text)
    if args.csv:
        with open(args.csv, "w") as fh:
            fh.write(csv_text + "\n")
        print(f"# wrote {len(shown)} points to {args.csv}",
              file=sys.stderr)
    mode = " frontier-only" if stats.mode == "frontier" else ""
    print(f"# sweep[{scn.name}]{mode} fabric: {stats.n_points_total} "
          f"points in {stats.n_chunks_total} chunks across "
          f"{stats.n_workers} workers; {stats.n_chunks_committed} "
          f"committed in {stats.elapsed_s:.1f}s", file=sys.stderr)
    if stats.mode == "frontier":
        print(f"# frontier: {len(records)} non-dominated points over "
              f"{'/'.join(scn.objectives)}", file=sys.stderr)
        if stats.n_frontier_overflowed:
            print(f"# warning: a worker's device frontier capacity "
                  f"overflowed ({stats.n_frontier_overflowed} candidates "
                  f"dropped); raise --frontier-cap", file=sys.stderr)
    if not stats.complete:
        print(f"# incomplete: resume with the same command (committed "
              f"chunks in {stats.out_dir} are never re-evaluated)",
              file=sys.stderr)
        return 1
    return 0


def _cmd_sweep_worker(args) -> int:
    """Lease-claiming fabric worker (repro.core.sweepfabric)."""
    from repro.core import sweepfabric

    rc = _validate_dispatch_args(args)
    if rc:
        return rc
    kwargs = {}
    if args.ttl is not None:
        kwargs["ttl_s"] = args.ttl
    if args.poll is not None:
        kwargs["poll_s"] = args.poll
    worker = sweepfabric.FabricWorker(
        args.dir, worker_id=args.id, claim_batch=args.claim_batch,
        superbatch=args.superbatch, eval_delay_s=args.eval_delay,
        max_chunks=args.max_chunks,
        compile_ahead=args.compile_ahead,
        bucketing=False if args.no_bucketing else None, **kwargs)
    stats = worker.run()
    print(f"# worker {stats.worker}: committed "
          f"{stats.n_chunks_committed} chunks ({stats.n_points} points) "
          f"in {stats.elapsed_s:.1f}s"
          + (f"; lost {stats.n_lost_leases} lease batch(es)"
             if stats.n_lost_leases else "")
          + ("; preempted (SIGTERM) — in-flight work committed"
             if stats.preempted else ""),
          file=sys.stderr)
    return 0


def _cmd_cooptimize(args) -> int:
    """Sweep -> refine pipeline (repro.core.cooptimize)."""
    import json
    import os

    from repro.core import cooptimize, scenarios, sweeprunner

    spec, records = sweeprunner.load_sweep(args.from_dir)
    if not records:
        # frontier-only sweep: seed refinement from the materialized
        # frontier (exactly the points worth refining anyway)
        fp = os.path.join(args.from_dir, "frontier.jsonl")
        if os.path.exists(fp):
            with open(fp) as fh:
                records = [json.loads(ln) for ln in fh if ln.strip()]
    if args.scenario is not None and args.scenario != spec.scenario:
        print(f"error: --scenario {args.scenario} contradicts the sweep "
              f"spec in {args.from_dir} (scenario={spec.scenario}); the "
              f"spec is authoritative — drop the flag", file=sys.stderr)
        return 2
    if args.scenario_param:
        want = _scenario_params_dict(args.scenario_param)
        have = dict(spec.scenario_params or {})
        if any(have.get(k) != v for k, v in want.items()):
            print(f"error: --scenario-param contradicts the sweep spec in "
                  f"{args.from_dir} (params={have}); the spec is "
                  f"authoritative — drop the flag", file=sys.stderr)
            return 2
    if args.objectives is not None \
            and tuple(args.objectives) != (spec.objectives or ()):
        print(f"error: --objectives {','.join(args.objectives)} "
              f"contradicts the sweep spec in {args.from_dir} "
              f"(objectives="
              f"{','.join(spec.objectives) if spec.objectives else '<default>'}"
              f"); the spec is authoritative — drop the flag",
              file=sys.stderr)
        return 2
    cfg = cooptimize.RefineConfig(
        top_k=args.top_k, candidates_per_seed=args.candidates,
        steps=args.steps, starts=args.starts, lr=args.lr, seed=args.seed)
    out_path = args.out or os.path.join(args.from_dir, "refined.jsonl")
    stats = cooptimize.refine_sweep((spec, records), cfg=cfg,
                                    out_path=out_path, verbose=False)
    scn = spec.scenario_spec.variants()[0].resolve()
    csv_text = sweeprunner.to_csv(stats.records, scn)
    print(csv_text)
    if args.csv:
        with open(args.csv, "w") as fh:
            fh.write(csv_text + "\n")
        print(f"# wrote {len(stats.records)} refined points to {args.csv}",
              file=sys.stderr)
    print(f"# cooptimize[{stats.scenario}]: {stats.n_records} sweep "
          f"records -> frontier {stats.n_frontier}; refined "
          f"{stats.n_candidates} candidates around {stats.n_seeds} seeds "
          f"({stats.n_objective_evals} objective evals, "
          f"{stats.n_unimproved} unimproved) in {stats.elapsed_s:.1f}s",
          file=sys.stderr)
    print(f"# {stats.n_dominating}/{stats.n_refined} refined points "
          f"dominate >=1 sweep frontier point; refined records -> "
          f"{stats.out_path}", file=sys.stderr)
    if stats.n_refined and not stats.n_dominating:
        print("# warning: no refined point dominates the sweep frontier "
              "(try more --steps/--starts)", file=sys.stderr)
    return 0


def _cmd_size(args) -> int:
    """Inverse fleet-sizing query (repro.core.traffic.size_fleet)."""
    import json
    import os

    from repro.core import sweeprunner, traffic

    if args.from_dir:
        # the swept records are authoritative: refuse contradicting flags
        # exactly as `sweep --resume` does
        ignored = [name for name, val, default in (
            ("--arch", args.arch, None), ("--cell", args.cell, None),
            ("--mesh", args.mesh, None), ("--logic", args.logic, ["N7"]),
            ("--hbm", args.hbm, ["HBM2E"]),
            ("--net", args.net, ["IB-NDR-X8"]),
            ("--scale", args.scale, None), ("--area", args.area, None),
            ("--power", args.power, None), ("--slo", args.slo, None),
            ("--scenario", args.scenario, "serving-traffic"),
            ("--scenario-param", args.scenario_param, None),
            ("--objectives", args.objectives, None),
            ("--tilings", args.tilings, 8),
            ("--profile", args.profile, None),
            ("--out", args.out, None),
        ) if val != default]
        if ignored:
            print(f"error: --from loads the sweep spec from "
                  f"{args.from_dir}/spec.json; drop these flags (they "
                  f"would be ignored): {', '.join(ignored)}",
                  file=sys.stderr)
            return 2
        spec, records = sweeprunner.load_sweep(args.from_dir)
        if not records:
            # frontier-only sweep: size over the materialized frontier
            fp = os.path.join(args.from_dir, "frontier.jsonl")
            if os.path.exists(fp):
                with open(fp) as fh:
                    records = [json.loads(ln) for ln in fh if ln.strip()]
    else:
        if not (args.arch and args.mesh):
            print("error: size needs --arch and --mesh (or --from DIR)",
                  file=sys.stderr)
            return 2
        profile_dict = None
        if args.profile is not None:
            from repro.calibrate import profiles as profiles_lib
            profile_dict = profiles_lib.load_profile(args.profile).to_dict()
        spec = sweeprunner.SweepSpec(
            arches=tuple(args.arch),
            mesh_shapes=tuple(tuple(m) for m in args.mesh),
            scenario=args.scenario, cells=tuple(args.cell or ()),
            logic_nodes=tuple(args.logic), hbms=tuple(args.hbm),
            nets=tuple(args.net),
            budget_scales=tuple(float(s) for s in args.scale) if args.scale
            else (1.0,),
            area_mm2=args.area, power_w=args.power, slo_s=args.slo,
            n_tilings=args.tilings, chunk_size=args.chunk_size,
            profile=profile_dict,
            scenario_params=_scenario_params_dict(args.scenario_param)
            or None,
            objectives=tuple(args.objectives) if args.objectives else None)
        runner = sweeprunner.SweepRunner(spec, out_dir=args.out,
                                         backend=args.backend)
        records = runner.run().records
    if spec.scenario != "serving-traffic":
        print(f"error: fleet sizing needs the serving-traffic scenario "
              f"(the sweep used {spec.scenario!r})", file=sys.stderr)
        return 2
    # model defaults = the spec's single-valued params; swept
    # (multi-valued) params override per record via the cell-id suffix
    base = dict(traffic.PARAM_DEFAULTS)
    base.update({k: v for k, v in spec.scenario_spec.params
                 if not isinstance(v, tuple)})
    if spec.slo_s is not None:
        base["slo_ttft_p99"] = spec.slo_s
    # objective-model params (energy price, MTBF, ...) are not traffic
    # params; split them out before the strict traffic parser
    from repro.core import objectives as objectives_lib
    _, base = objectives_lib.split_objective_params(base)
    tm, pol, spec_slo = traffic.split_params(base)
    slo = {name: float(v) for name in
           ("ttft_p50", "ttft_p99", "tpot_p50", "tpot_p99")
           if (v := getattr(args, "slo_" + name)) is not None}
    if not slo:         # fall back to the walls the sweep itself carried
        slo = {k[len("slo_"):]: float(v) for k, v in spec_slo.items()
               if v is not None}
    if not slo:
        print("error: size needs at least one SLO wall (--slo-ttft-p99 "
              "0.5, --slo-tpot-p50 0.05, ...)", file=sys.stderr)
        return 2
    plan = traffic.size_fleet(records, args.qps, slo=slo, traffic=tm,
                              policy=pol, top_k=args.top_k,
                              rank_by=args.rank_by)
    walls = " ".join(f"{k}<={v:g}s" for k, v in sorted(slo.items()))
    print(f"# size: {plan.n_records} serving-traffic records, "
          f"{plan.n_sized} sizeable under {walls} at {plan.qps:g} qps "
          f"({plan.n_unsizeable} unsizeable; {plan.n_evals} closed-form "
          f"evals, zero sweep re-evaluations)", file=sys.stderr)
    if plan.best is None:
        print("# no swept design meets the SLO walls at any replica "
              "count", file=sys.stderr)
        return 1
    rank_col = traffic.RANK_COLUMNS[args.rank_by]
    header = ("devices,replicas,devices_per_replica,per_replica_qps,"
              "ttft_p99_s,tpot_p50_s,util,key")
    if rank_col is not None:       # default devices output stays identical
        header += f",{rank_col}"
    print(header)
    for c in plan.candidates:
        m = c.metrics
        row = (f"{c.devices},{c.replicas},{c.devices_per_replica},"
               f"{c.per_replica_qps:.4g},{m['ttft_p99_s']:.4g},"
               f"{m['tpot_p50_s']:.4g},{m['util']:.3f},{c.key}")
        if rank_col is not None:
            row += f",{c.rank_value:.6g}" if c.rank_value is not None \
                else ","
        print(row)
    b = plan.best
    print(f"# best: {b.devices} devices = {b.replicas} replicas x "
          f"{b.devices_per_replica} ({b.key}) -> ttft_p99 "
          f"{b.metrics['ttft_p99_s']:.4g}s, tpot_p50 "
          f"{b.metrics['tpot_p50_s']:.4g}s at {b.per_replica_qps:.4g} "
          f"qps/replica", file=sys.stderr)
    return 0


def _cmd_explore(args) -> int:
    """Surrogate + acquisition-driven exploration (repro.core.surrogate)."""
    from repro.core import surrogate, sweeprunner

    cfg = surrogate.ExploreConfig(
        eval_budget=args.eval_budget, eval_frac=args.eval_frac,
        init_chunks=args.init_chunks, batch_chunks=args.batch_chunks,
        stagnation=args.stagnation, acquisition=args.acquisition,
        kappa=args.kappa,
        surrogate=surrogate.SurrogateConfig(
            ensemble=args.ensemble, hidden=args.hidden, steps=args.steps,
            lr=args.lr, seed=args.seed))

    train_records = None
    if args.train_from:
        _, train_records = surrogate.load_training_records(args.train_from)
        if not train_records:
            print(f"error: no committed records in {args.train_from}",
                  file=sys.stderr)
            return 2
        print(f"# surrogate: seeded with {len(train_records)} records "
              f"from {args.train_from}", file=sys.stderr)

    # axis/scenario flags are meaningless when the spec comes from a
    # directory; refuse them instead of silently ignoring them
    spec_from_dir = args.resume or args.order_dir
    if spec_from_dir:
        src = args.order_dir or args.out
        ignored = [name for name, val, default in (
            ("--arch", args.arch, None), ("--cell", args.cell, None),
            ("--mesh", args.mesh, None), ("--logic", args.logic, ["N7"]),
            ("--hbm", args.hbm, ["HBM2E"]),
            ("--net", args.net, ["IB-NDR-X8"]),
            ("--scale", args.scale, None), ("--area", args.area, None),
            ("--power", args.power, None), ("--slo", args.slo, None),
            ("--scenario", args.scenario, "train"),
            ("--chunk-size", args.chunk_size, 8),
            ("--tilings", args.tilings, 8),
            ("--profile", args.profile, None),
            ("--scenario-param", args.scenario_param, None),
            ("--objectives", args.objectives, None),
        ) if val != default]
        if ignored:
            print(f"error: the spec is loaded from {src}/spec.json; drop "
                  f"these flags (they would be ignored): "
                  f"{', '.join(ignored)}", file=sys.stderr)
            return 2

    if args.order_dir:
        # ranking-only mode: no real evaluations, just DIR/order.json
        if args.out or args.resume:
            print("error: --order-dir ranks an existing fabric "
                  "directory; it is incompatible with --out/--resume",
                  file=sys.stderr)
            return 2
        from repro.core import sweepfabric
        _, fabric = sweepfabric.load_dir(args.order_dir)
        if fabric.get("mode") == "frontier":
            committed, _, _ = sweepfabric.merge_frontier(args.order_dir)
        else:
            committed, _ = sweepfabric.merge_results(args.order_dir)
        rows = list(train_records or []) + list(committed)
        if not rows:
            print(f"error: nothing to train on — {args.order_dir} has no "
                  f"committed chunks yet; seed with --train-from DIR",
                  file=sys.stderr)
            return 2
        order = surrogate.order_fabric_dir(args.order_dir, rows, cfg=cfg)
        print(f"# explore: wrote advisory order for {len(order)} chunks "
              f"-> {args.order_dir}/order.json (trained on {len(rows)} "
              f"records); workers claim frontier-adjacent chunks first",
              file=sys.stderr)
        head = ",".join(str(i) for i in order[:8])
        print(f"# explore: first claims: {head}"
              + (",..." if len(order) > 8 else ""), file=sys.stderr)
        return 0

    if args.resume:
        if not args.out:
            print("error: --resume requires --out DIR", file=sys.stderr)
            return 2
        spec, _ = surrogate.load_training_records(args.out)
    else:
        if not (args.arch and args.mesh):
            print("error: explore needs --arch and --mesh (or --resume "
                  "with --out / --order-dir DIR)", file=sys.stderr)
            return 2
        profile_dict = None
        if args.profile is not None:
            from repro.calibrate import profiles as profiles_lib
            profile_dict = profiles_lib.load_profile(args.profile).to_dict()
        spec = sweeprunner.SweepSpec(
            arches=tuple(args.arch),
            mesh_shapes=tuple(tuple(m) for m in args.mesh),
            scenario=args.scenario, cells=tuple(args.cell or ()),
            logic_nodes=tuple(args.logic), hbms=tuple(args.hbm),
            nets=tuple(args.net),
            budget_scales=tuple(float(s) for s in args.scale)
            if args.scale else (1.0,),
            area_mm2=args.area, power_w=args.power, slo_s=args.slo,
            n_tilings=args.tilings, chunk_size=args.chunk_size,
            profile=profile_dict,
            scenario_params=_scenario_params_dict(args.scenario_param)
            or None,
            objectives=tuple(args.objectives) if args.objectives else None)

    stats = surrogate.explore(spec, out_dir=args.out, cfg=cfg,
                              resume=args.resume,
                              train_records=train_records, verbose=True)
    scn = spec.scenario_spec.variants()[0].resolve()
    csv_text = sweeprunner.to_csv(stats.frontier, scn)
    print(csv_text)
    if args.csv:
        with open(args.csv, "w") as fh:
            fh.write(csv_text + "\n")
        print(f"# wrote {len(stats.frontier)} frontier points to "
              f"{args.csv}", file=sys.stderr)
    frac = stats.n_points_evaluated / max(stats.n_points_total, 1)
    print(f"# explore[{scn.name}] acq={cfg.acquisition}: evaluated "
          f"{stats.n_points_evaluated}/{stats.n_points_total} points "
          f"({frac:.0%}) in {stats.n_chunks_evaluated} chunks "
          f"(+{stats.n_chunks_skipped} resumed) over {stats.rounds} "
          f"rounds in {stats.elapsed_s:.1f}s; stop={stats.stop}",
          file=sys.stderr)
    print(f"# frontier: {len(stats.frontier)} non-dominated points over "
          f"{'/'.join(stats.objectives)}", file=sys.stderr)
    if stats.out_dir:
        print(f"# continue with `python -m repro.pathfind explore --out "
              f"{stats.out_dir} --resume`, or exhaust the grid with "
              f"`sweep --out {stats.out_dir} --resume`", file=sys.stderr)
    return 0


def _template_arch(tech: str):
    from repro.core import age
    return age.cpu_host_microarch() if tech == "cpu_host" \
        else age.tpu_v5e_microarch()


def _cmd_calibrate(args) -> int:
    """Measure -> fit -> profile.json + report.json (repro.calibrate)."""
    import os

    from repro.calibrate import fitting, microbench, profiles, report
    from repro.core.roofline import PPEConfig

    spec = microbench.default_spec(args.suite, reps=args.reps)
    runner = microbench.MicrobenchRunner(spec, out_dir=args.out)
    stats = runner.run(resume=args.resume, verbose=True)
    print(f"# measured {stats.n_measured} points "
          f"(skipped {stats.n_skipped} existing) in {stats.elapsed_s:.1f}s",
          file=sys.stderr)
    if not stats.records:
        print("error: no measurements", file=sys.stderr)
        return 2

    template = _template_arch(args.tech)
    ppe = PPEConfig(n_tilings=args.tilings)
    res = fitting.fit(stats.records, template, ppe=ppe,
                      cfg=fitting.FitConfig(steps=args.steps,
                                            starts=args.starts,
                                            seed=args.seed))
    base_rep = report.validation_report(stats.records, template, ppe=ppe)
    cal_rep = report.validation_report(stats.records, template,
                                       params=res.params, ppe=ppe)
    profile = profiles.CalibrationProfile(
        tech=args.tech, params=res.params,
        measure_fingerprint=spec.fingerprint(),
        fit={"mre": res.mre, "mre_uncalibrated": res.mre_identity,
             "loss": res.loss, "loss_uncalibrated": res.loss_identity,
             "selected": res.selected, "n_evals": res.n_evals,
             "n_measurements": len(stats.records),
             "n_tilings": args.tilings},
        validation={"uncalibrated": base_rep["overall"],
                    "calibrated": cal_rep["overall"]})
    ppath = os.path.join(args.out, "profile.json")
    profiles.save_profile(profile, ppath)
    report.save_baseline(cal_rep, os.path.join(args.out, "report.json"))

    print(report.format_report(cal_rep, baseline=base_rep))
    print(f"# fit[{res.selected}]: MRE {res.mre_identity * 100:.1f}% -> "
          f"{res.mre * 100:.1f}% over {res.n_evals} objective evals",
          file=sys.stderr)
    print(f"# profile -> {ppath}; baseline report -> "
          f"{os.path.join(args.out, 'report.json')}", file=sys.stderr)
    if not res.improved:
        print("# warning: calibration did not improve on the "
              "uncalibrated techlib entry", file=sys.stderr)
    return 0


def _cmd_validate(args) -> int:
    """Fresh validation report + drift detection vs the stored baseline."""
    import os

    from repro.calibrate import microbench, profiles, report
    from repro.core.roofline import PPEConfig

    ppath = args.profile or os.path.join(args.out, "profile.json")
    bpath = args.baseline or os.path.join(args.out, "report.json")
    profile = profiles.load_profile(ppath)
    if args.remeasure:
        runner = microbench.MicrobenchRunner.from_dir(args.out)
        spec = runner.spec
        records = microbench.MicrobenchRunner(spec).run().records
    else:
        records = microbench.load_measurements(args.out)
    if not records:
        print(f"error: no measurements in {args.out}", file=sys.stderr)
        return 2
    template = _template_arch(profile.tech)
    # tilings must match the fit-time sampling or every group's MRE
    # shifts and the drift gate fires with nothing actually changed
    tilings = args.tilings if args.tilings is not None \
        else int(profile.fit.get("n_tilings", 8))
    ppe = PPEConfig(n_tilings=tilings)
    cal_rep = report.validation_report(records, template,
                                       params=profile.params, ppe=ppe)
    base_rep = report.validation_report(records, template, ppe=ppe)
    print(report.format_report(cal_rep, baseline=base_rep))
    stored = report.load_baseline(bpath) if os.path.exists(bpath) else None
    if args.update_baseline or stored is None:
        report.save_baseline(cal_rep, bpath)
        print(f"# baseline written -> {bpath}", file=sys.stderr)
        return 0
    drift = report.check_drift(cal_rep, stored, tol=args.drift_tol)
    if drift:
        for msg in drift:
            print(f"# DRIFT: {msg}", file=sys.stderr)
        return 1
    print(f"# no drift vs {bpath} (tol "
          f"{args.drift_tol * 100:.0f} points)", file=sys.stderr)
    return 0


def _cmd_plan(args) -> int:
    from repro.configs.base import SHAPE_CELLS, get_config
    from repro.core import planner

    axes = ("pod", "data", "model")[-len(args.mesh):]
    plan = planner.plan(get_config(args.arch), SHAPE_CELLS[args.cell],
                        args.mesh, axes)
    print(f"strategy       {plan.strategy.name}")
    print(f"predicted_step {plan.predicted_step_s*1e3:.3f} ms")
    for k, v in plan.predicted_breakdown.items():
        print(f"  {k:15s} {v*1e3:.3f} ms")
    for axis, rule in plan.rules:
        print(f"rule {axis:10s} -> {rule}")
    if plan.notes:
        print(f"notes: {plan.notes}")
    return 0


def _cmd_soe(args) -> int:
    from repro.configs.base import SHAPE_CELLS, get_config
    from repro.core import lmgraph, soe, techlib
    from repro.core.roofline import PPEConfig

    tech = techlib.make_tech_config(args.logic, args.hbm, args.net)
    g = lmgraph.build_graph(get_config(args.arch), SHAPE_CELLS[args.cell])
    res = soe.co_optimize(
        tech, g, n_devices=args.devices,
        cfg=soe.SOEConfig(steps=args.steps, starts=args.starts),
        search_arch=not args.no_search_arch,
        ppe=PPEConfig(n_tilings=args.tilings))
    print(f"strategy  {res.strategy.name}")
    print(f"time      {res.time_s*1e3:.3f} ms/iter")
    print(f"queries   {res.n_queries}")
    for comp, frac in res.budgets.area_frac.items():
        print(f"area[{comp:9s}] {float(frac):.3f}")
    return 0


def main(argv=None) -> int:
    args = _parser().parse_args(argv)
    # compiled executables persist in one fixed place (repro.devices)
    devices.enable_compilation_cache()
    try:
        return {"sweep": _cmd_sweep, "sweep-worker": _cmd_sweep_worker,
                "plan": _cmd_plan,
                "soe": _cmd_soe, "calibrate": _cmd_calibrate,
                "validate": _cmd_validate, "size": _cmd_size,
                "explore": _cmd_explore,
                "cooptimize": _cmd_cooptimize}[args.cmd](args)
    except ModuleNotFoundError as e:
        print(f"error: unknown arch (no config module): {e.name}",
              file=sys.stderr)
    except KeyError as e:
        print(f"error: unknown name: {e}", file=sys.stderr)
    except (ValueError, AttributeError, OSError,
            devices.AcceleratorBusyError) as e:
        print(f"error: {e}", file=sys.stderr)
    return 2


if __name__ == "__main__":
    sys.exit(main())
