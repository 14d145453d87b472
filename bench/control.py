#!/usr/bin/env python3
"""Readings that the correctness limits are set from, on the chip.

    python3 bench/control.py --workload <name> --seeds 11,12,13 --seconds 5

One process sets the cell up once, then for each seed serves a short
window of the cell's traffic and prints one JSON line with:

- ``program``: each number the check compares, as a run with that seed
  would read it (the lower readings);
- ``control``: the same numbers with the reference, computed in bfloat16
  on the chip (`Reference.control_record`), answering in the program's
  place (the upper readings).

The benchmark's own runs never run this.  Without a TPU it exits
non-zero.
"""

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
_platforms = os.environ.get("JAX_PLATFORMS", "")
if _platforms and "cpu" not in _platforms.split(","):
    os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)

    import jax.numpy as jnp

    from bench import drive, generator, harness, manifest, reference
    from repro import devices

    cell = manifest.workload(manifest.load(), args.workload)
    devs, why = harness.check_device(int(cell["chips"]))
    if devs is None:
        print(f"control: {why}", file=sys.stderr)
        return 2
    devices.enable_compilation_cache()
    config = manifest.config(cell["config"])
    mix = manifest.traffic(cell["traffic"])
    workdir = os.path.join(harness.WORKDIR, "control." + cell["name"])
    seeds = [int(s) for s in args.seeds.split(",")]
    warm = drive.Driver(config, mix, workdir)
    warm.warmup(generator.Requests(mix, seeds[0] ^ 0x5A5A).warmup())
    ref = reference.Reference(config, warm.grid)
    print(f"set-up {time.time() - T_START:.1f} s", file=sys.stderr,
          flush=True)
    for seed in seeds:
        shutil.rmtree(workdir, ignore_errors=True)
        driver = drive.Driver(config, mix, workdir)
        reqs = generator.Requests(mix, seed)
        reqs.warmup()                   # the draws a run's set-up takes
        _, failed, window_s = harness.serve_window(driver, reqs,
                                                   args.seconds)
        t0 = time.time()
        program = harness.check_run(driver, seed, workdir).items
        t1 = time.time()
        control = harness.check_run(
            driver, seed, workdir,
            stand_in=lambda lb: ref.control_record(lb, jnp.bfloat16)).items
        print(json.dumps({"workload": cell["name"], "seed": seed,
                          "requests": len(driver.done), "failed": failed,
                          "window_s": window_s, "check_s": t1 - t0,
                          "control_s": time.time() - t1,
                          "program": program, "control": control}),
              flush=True)
    shutil.rmtree(workdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
