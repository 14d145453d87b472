"""The benchmark's command refuses to measure where it cannot: without a
TPU, and in a directory that holds only the benchmark's own files."""

import json
import os
import shutil
import subprocess
import sys

from bench import manifest

CELL = manifest.load()["workloads"][0]["name"]


def _run(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update({"JAX_PLATFORMS": "cpu", **(env_extra or {})})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL, "--seed",
         str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result_line(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "metrics" in json.loads(line):
                return False
        except (ValueError, TypeError):
            continue
    return True


def test_refuses_without_a_tpu():
    proc = _run(manifest.ROOT)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert _no_result_line(proc.stdout)


def test_refuses_with_only_the_benchmark_files(tmp_path):
    bench = manifest.load()
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), tmp_path)
    for p in bench["paths"]:
        shutil.copytree(os.path.join(manifest.ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(str(tmp_path))
    assert proc.returncode != 0
    assert _no_result_line(proc.stdout)


def test_unknown_workload_is_an_error():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "no.such.cell",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0 and _no_result_line(proc.stdout)
