"""Calibration & validation subsystem tests (ISSUE-4 tentpole).

Covers: measurement spec fingerprints + deterministic enumeration, the
resumable microbench runner (zero re-measurement), the differentiable fit
recovering synthetic ground-truth parameters, profile round-trip and
MicroArch application, validation reports + drift detection, profile
embedding in SweepSpec (fingerprint identity + calibrated hardware), and
the slow-lane CLI flow calibrate -> validate -> sweep --profile.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.calibrate import fitting, microbench, profiles, report
from repro.calibrate.microbench import MeasureSpec, MicrobenchRunner
from repro.core import age, sweeprunner
from repro.core.roofline import PPEConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = MeasureSpec(suite="quick", gemm_shapes=((64, 64, 64), (64, 64, 128),
                                               (128, 128, 128)), reps=1)
PPE = PPEConfig(n_tilings=4)


def _env():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(REPO, "src"),
                    env.get("PYTHONPATH", "")) if p)
    return env


def _synthetic_records(spec, template, true_params, noise=0.0, seed=0):
    """Measurements generated from the model itself (known ground truth)."""
    recs = [{"key": p.key(), "kind": p.kind, **dict(p.params)}
            for p in microbench.enumerate_points(spec)]
    pred = fitting.predict_measurements(recs, template, params=true_params,
                                        ppe=PPE)
    rng = np.random.default_rng(seed)
    for r, t in zip(recs, pred):
        jitter = rng.uniform(1 - noise, 1 + noise) if noise else 1.0
        r["t_s"] = float(t) * jitter
        r["t_mean_s"] = r["t_s"]
        r["flops"] = 2.0 * r["m"] * r["n"] * r["k"]
    return recs


# ----------------------------------------------------------- spec/enumerate
def test_measure_spec_fingerprint_roundtrip():
    assert MeasureSpec.from_dict(TINY.to_dict()) == TINY
    assert MeasureSpec.from_dict(TINY.to_dict()).fingerprint() \
        == TINY.fingerprint()
    other = MeasureSpec(suite="quick", gemm_shapes=((64, 64, 64),), reps=1)
    assert other.fingerprint() != TINY.fingerprint()
    # the shipped suites enumerate deterministically with unique keys
    for suite in ("quick", "full"):
        pts = microbench.enumerate_points(microbench.default_spec(suite))
        assert pts == microbench.enumerate_points(
            microbench.default_spec(suite))
        keys = [p.key() for p in pts]
        assert len(set(keys)) == len(keys)


def test_full_suite_covers_all_kinds():
    kinds = {p.kind for p in microbench.enumerate_points(
        microbench.default_spec("full"))}
    assert kinds == set(microbench.KINDS)


# ----------------------------------------------------------------- runner
def test_runner_resume_zero_remeasurement(tmp_path, monkeypatch):
    calls = []

    def fake_measure(pt, spec):
        calls.append(pt.key())
        return {"key": pt.key(), "kind": pt.kind, **dict(pt.params),
                "reps": spec.reps, "t_s": 1e-3, "t_mean_s": 1e-3,
                "flops": 1.0, "bytes": 1.0}

    monkeypatch.setattr(microbench, "measure_point", fake_measure)
    out = str(tmp_path / "cal")
    stats = MicrobenchRunner(TINY, out_dir=out).run()
    assert stats.n_measured == 3 and len(calls) == 3
    # a fresh run into the same dir must refuse (measurements exist)
    with pytest.raises(FileExistsError):
        MicrobenchRunner(TINY, out_dir=out).run()
    # resume re-measures nothing
    calls.clear()
    stats2 = MicrobenchRunner(TINY, out_dir=out).run(resume=True)
    assert stats2.n_measured == 0 and stats2.n_skipped == 3
    assert calls == []
    # drop one record (simulated partial run) -> only that one re-measured
    mpath = os.path.join(out, "measurements.jsonl")
    lines = open(mpath).read().strip().splitlines()
    with open(mpath, "w") as fh:
        fh.write("\n".join(lines[:-1]) + "\n")
    stats3 = MicrobenchRunner(TINY, out_dir=out).run(resume=True)
    assert stats3.n_measured == 1 and len(calls) == 1
    # a changed spec must refuse the directory
    other = MeasureSpec(suite="quick", gemm_shapes=((32, 32, 32),), reps=1)
    with pytest.raises(ValueError, match="spec changed"):
        MicrobenchRunner(other, out_dir=out).run(resume=True)
    # loader returns every record in spec order
    recs = microbench.load_measurements(out)
    assert [r["key"] for r in recs] \
        == [p.key() for p in microbench.enumerate_points(TINY)]


def test_collectives_on_accelerator_refused_never_sent_to_cpu(
        monkeypatch, capsys):
    """One chip cannot host a 2-device collective: the points are refused
    and named, and no forced-CPU child measures them in the chip's place."""
    import jax

    spec = MeasureSpec(suite="full", collective_bytes=(1 << 16,),
                       collective_devices=2, reps=1)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "local_device_count", lambda: 1)
    monkeypatch.setattr(microbench, "_collective_subprocess",
                        lambda *a: pytest.fail("forced-CPU child started"))
    monkeypatch.setattr(microbench, "measure_point",
                        lambda *a: pytest.fail("collective measured"))
    got = []
    n = microbench.run_points(microbench.enumerate_points(spec), spec,
                              got.append)
    assert n == 0 and got == []
    assert "refused 1 collective points" in capsys.readouterr().err


# ------------------------------------------------------------------- fit
def test_fit_recovers_synthetic_ground_truth():
    template = age.cpu_host_microarch()
    true = fitting.default_params()
    true["compute_eff"] = 0.5
    true["kernel_overhead_s"] = 5e-5
    recs = _synthetic_records(TINY, template, true, noise=0.03)
    res = fitting.fit(recs, template, ppe=PPE,
                      cfg=fitting.FitConfig(steps=40, starts=3))
    assert res.improved
    assert res.mre < 0.15 < res.mre_identity
    assert 0.35 < res.params["compute_eff"] < 0.7
    assert res.n_evals > 0 and res.selected in ("seed", "fit")


def test_fit_identity_never_beaten_by_selection():
    """On measurements generated exactly by the identity parameters the
    selected candidate can't validate worse than identity."""
    template = age.cpu_host_microarch()
    recs = _synthetic_records(TINY, template, fitting.default_params())
    res = fitting.fit(recs, template, ppe=PPE,
                      cfg=fitting.FitConfig(steps=10, starts=2))
    assert res.mre <= res.mre_identity + 1e-12


def test_predictor_rejects_unknown_kind():
    template = age.cpu_host_microarch()
    with pytest.raises(ValueError, match="unknown measurement kind"):
        fitting.build_predictor([{"kind": "nope", "t_s": 1.0}], template)


# ------------------------------------------------------------- decode_step
def test_decode_step_measured_predicted_and_fitted():
    """ISSUE-5 satellite: the KV-cache-read-bound decode step is a
    first-class microbench kind — measured on the real model's
    `decode_step` over a full cache, predicted through the decode-kind
    lmgraph, and part of the default fit groups."""
    assert "decode_step" in microbench.KINDS
    assert "decode_step" in fitting.KINDS_FITTED
    full = microbench.default_spec("full")
    assert "decode_step" in full.model_phases
    spec = MeasureSpec(suite="dec", model_archs=("qwen1.5-0.5b",),
                       model_phases=("decode_step",), reps=1)
    pts = microbench.enumerate_points(spec)
    assert [p.kind for p in pts] == ["decode_step"]
    cell = microbench.model_cell(pts[0])
    assert cell.kind == "decode"
    rec = microbench.measure_point(pts[0], spec)
    assert rec["kind"] == "decode_step" and rec["t_s"] > 0
    assert rec["bytes"] > 0                # KV read volume is the traffic
    template = age.cpu_host_microarch()
    pred = fitting.predict_measurements([rec], template, ppe=PPE)
    assert np.isfinite(pred).all() and (pred > 0).all()
    # the fitter consumes the record (its group appears in the report)
    from repro.calibrate import report
    rep = report.validation_report([rec], template, ppe=PPE)
    assert "decode_step:qwen1.5-0.5b" in rep["groups"]
    assert rep["overall"]["n"] == 1        # fitted kind -> in the overall


# --------------------------------------------------------------- profiles
def test_profile_roundtrip_and_apply(tmp_path):
    template = age.cpu_host_microarch()
    params = fitting.default_params()
    params["compute_eff"] = 2.0
    params["dram_bw_eff"] = 0.5
    params["kernel_overhead_s"] = 1e-4
    prof = profiles.CalibrationProfile(tech="cpu_host", params=params,
                                       fit={"mre": 0.1})
    path = str(tmp_path / "profile.json")
    profiles.save_profile(prof, path)
    back = profiles.load_profile(path)
    assert back == prof
    arch = profiles.apply_profile(template, back)
    assert float(arch.compute_throughput) \
        == pytest.approx(2.0 * float(template.compute_throughput))
    assert float(arch.dram_bw) \
        == pytest.approx(0.5 * float(template.dram_bw))
    # identity profile is a no-op; None passes through
    same = profiles.apply_profile(template, profiles.identity_profile())
    assert float(same.compute_throughput) \
        == pytest.approx(float(template.compute_throughput))
    assert profiles.apply_profile(template, None) is template
    # PPE overhead override
    ppe = profiles.ppe_with_profile(PPE, back)
    assert ppe.kernel_overhead_s == pytest.approx(1e-4)
    assert profiles.ppe_with_profile(PPE, None) is PPE


# ---------------------------------------------------------------- reports
def test_validation_report_and_drift(tmp_path):
    template = age.cpu_host_microarch()
    true = fitting.default_params()
    true["compute_eff"] = 0.5
    recs = _synthetic_records(TINY, template, true)
    base = report.validation_report(recs, template, ppe=PPE)
    cal = report.validation_report(recs, template, params=true, ppe=PPE)
    assert cal["groups"]["gemm"]["mre"] < base["groups"]["gemm"]["mre"]
    assert cal["overall"]["mre"] == pytest.approx(0.0, abs=1e-6)
    cmp = report.compare_reports(base, cal)
    assert cmp["gemm"]["improved"] and cmp["overall"]["improved"]
    text = report.format_report(cal, baseline=base)
    assert "gemm" in text and "OVERALL(fitted)" in text
    # drift: no messages against itself, messages against a worse report
    assert report.check_drift(cal, cal) == []
    msgs = report.check_drift(base, cal, tol=0.05)
    assert msgs and any("gemm" in m for m in msgs)
    # missing group detection
    missing = {"groups": {}, "overall": cal["overall"]}
    assert any("missing" in m for m in report.check_drift(missing, cal))
    # baseline round-trip
    path = str(tmp_path / "report.json")
    report.save_baseline(cal, path)
    assert report.load_baseline(path)["groups"]["gemm"]["n"] == 3


# ----------------------------------------------------- sweep integration
def test_sweepspec_profile_changes_fingerprint_and_hardware():
    base = sweeprunner.SweepSpec(arches=("qwen1.5-0.5b",),
                                 mesh_shapes=((2, 2),), n_tilings=4)
    params = fitting.default_params()
    params["dram_bw_eff"] = 0.25
    params["kernel_overhead_s"] = 7e-5
    prof = profiles.CalibrationProfile(tech="cpu_host", params=params)
    import dataclasses
    calib = dataclasses.replace(base, profile=prof.to_dict())
    # a profile-less spec keys byte-identically to pre-profile specs
    assert "profile" not in base.to_dict()
    assert base.fingerprint() != calib.fingerprint()
    rt = sweeprunner.SweepSpec.from_dict(calib.to_dict())
    assert rt.fingerprint() == calib.fingerprint()
    # hardware resolution applies the profile (distinct cache entries)
    hw_plain = sweeprunner._hardware(base, "N7", "HBM2E", "IB-NDR-X8", 1.0)
    hw_cal = sweeprunner._hardware(calib, "N7", "HBM2E", "IB-NDR-X8", 1.0)
    assert float(hw_cal.dram_bw) \
        == pytest.approx(0.25 * float(hw_plain.dram_bw))
    # and the spec's PPE carries the fitted kernel overhead
    assert sweeprunner.spec_ppe(calib).kernel_overhead_s \
        == pytest.approx(7e-5)
    assert sweeprunner.spec_ppe(base).kernel_overhead_s \
        == PPEConfig().kernel_overhead_s


def test_calibrated_sweep_records_differ():
    spec = sweeprunner.SweepSpec(arches=("qwen1.5-0.5b",),
                                 mesh_shapes=((2, 2),), n_tilings=4)
    params = fitting.default_params()
    params["compute_eff"] = 0.5
    prof = profiles.CalibrationProfile(tech="cpu_host", params=params)
    import dataclasses
    calib = dataclasses.replace(spec, profile=prof.to_dict())
    plain_recs = sweeprunner.SweepRunner(spec, backend="serial").run() \
        .records
    cal_recs = sweeprunner.SweepRunner(calib, backend="serial").run() \
        .records
    assert len(plain_recs) == len(cal_recs) >= 1
    assert cal_recs[0]["time_s"] != pytest.approx(plain_recs[0]["time_s"])


# ------------------------------------------------------------------- CLI
@pytest.mark.slow
def test_cli_calibrate_validate_sweep(tmp_path):
    """The acceptance flow: calibrate -> validate -> sweep --profile."""
    out = str(tmp_path / "calib")
    cal = subprocess.run(
        [sys.executable, "-m", "repro.pathfind", "calibrate",
         "--out", out, "--suite", "quick", "--reps", "1",
         "--steps", "40", "--starts", "3"],
        env=_env(), capture_output=True, text=True, cwd=REPO, timeout=420)
    assert cal.returncode == 0, cal.stderr
    prof = json.load(open(os.path.join(out, "profile.json")))
    # acceptance: strictly lower MRE than the uncalibrated techlib entry
    assert prof["fit"]["mre"] < prof["fit"]["mre_uncalibrated"]
    assert os.path.exists(os.path.join(out, "report.json"))

    # resume measures nothing new
    resumed = subprocess.run(
        [sys.executable, "-m", "repro.pathfind", "calibrate",
         "--out", out, "--suite", "quick", "--reps", "1", "--resume",
         "--steps", "5", "--starts", "2"],
        env=_env(), capture_output=True, text=True, cwd=REPO, timeout=420)
    assert resumed.returncode == 0, resumed.stderr
    assert "measured 0 points" in resumed.stderr

    val = subprocess.run(
        [sys.executable, "-m", "repro.pathfind", "validate", "--out", out],
        env=_env(), capture_output=True, text=True, cwd=REPO, timeout=420)
    assert val.returncode == 0, val.stderr
    assert "no drift" in val.stderr

    sweep_dir = str(tmp_path / "sweep")
    sw = subprocess.run(
        [sys.executable, "-m", "repro.pathfind", "sweep",
         "--arch", "qwen1.5-0.5b", "--mesh", "2x2", "--tilings", "4",
         "--backend", "serial", "--out", sweep_dir,
         "--profile", os.path.join(out, "profile.json")],
        env=_env(), capture_output=True, text=True, cwd=REPO, timeout=420)
    assert sw.returncode == 0, sw.stderr
    head = json.load(open(os.path.join(sweep_dir, "spec.json")))
    assert head["spec"]["profile"]["params"]
    rows = [json.loads(ln) for ln in
            open(os.path.join(sweep_dir, "results.jsonl"))]
    assert rows and all(r.get("time_s") for r in rows)
    # --resume refuses a contradicting --profile (spec is authoritative)
    refused = subprocess.run(
        [sys.executable, "-m", "repro.pathfind", "sweep",
         "--out", sweep_dir, "--resume",
         "--profile", os.path.join(out, "profile.json")],
        env=_env(), capture_output=True, text=True, cwd=REPO, timeout=420)
    assert refused.returncode == 2
    assert "--profile" in refused.stderr
