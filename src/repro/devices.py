"""Process-level JAX setup shared by every entry point.

Two rules hold on a host with an accelerator (a TPU):

* **The compile cache has one fixed place.**  JAX's persistent
  compilation cache only hits when the directory stays put between runs.
  `enable_compilation_cache` uses ``JAX_COMPILATION_CACHE_DIR`` when it is
  set, and ``<checkout>/.jax_cache`` otherwise.  The CLI, the fabric
  workers (through the CLI), the launchers and ``chip_smoke.py`` call it;
  library code never places the cache.
* **One process holds the chip.**  A parent that has touched JAX holds the
  accelerator, and a child that needs it then fails or hangs.
  `check_children_platform` refuses to start JAX worker processes unless
  they would run on the CPU.

This module imports JAX lazily so that ``pathfind --help`` stays cheap.
"""

from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path
from typing import Mapping, Optional

# src/repro/devices.py -> the checkout root
CACHE_DIR = str(Path(__file__).resolve().parents[2] / ".jax_cache")

_PROBE = "import jax; print(jax.default_backend())"


def compilation_cache_dir() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` if set, else the checkout's cache."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR


def enable_compilation_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory.

    Every compile is cached (no size floor, 0.5 s compile-time floor), so a
    later process skips the XLA compile of each executable it shares.
    Returns the directory.
    """
    import jax
    path = compilation_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return path


class AcceleratorBusyError(RuntimeError):
    """JAX worker processes were refused: they would need the accelerator
    that one process must hold alone."""


def children_platform(env: Optional[Mapping[str, str]] = None) -> str:
    """The JAX platform a child started with ``env`` would run on.

    Decided without initializing a backend in this process: a backend this
    process already holds answers directly; ``JAX_PLATFORMS=cpu`` in the
    child's environment means the CPU; otherwise a short-lived probe
    process asks JAX and exits before any worker starts.
    """
    from jax._src import xla_bridge
    env = dict(os.environ if env is None else env)
    if xla_bridge.backends_are_initialized():
        import jax
        if jax.default_backend() != "cpu":
            return jax.default_backend()
    if env.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return "cpu"
    proc = subprocess.run([sys.executable, "-c", _PROBE], env=env,
                          capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        raise RuntimeError(f"JAX platform probe failed: {proc.stderr}")
    return proc.stdout.split()[-1]


def check_children_platform(what: str,
                            env: Optional[Mapping[str, str]] = None) -> None:
    """Refuse to start JAX worker processes on an accelerator host.

    Raises `AcceleratorBusyError` naming ``what`` and the platform before
    any child is started.
    """
    platform = children_platform(env)
    if platform != "cpu":
        raise AcceleratorBusyError(
            f"{what} starts one JAX process per worker, but JAX runs on "
            f"'{platform}' here: an accelerator belongs to one process at "
            f"a time, so the workers would contend for it.  Run a single "
            f"process instead (the pipeline backend drives every local "
            f"device), or start one `pathfind sweep-worker` per host.")
