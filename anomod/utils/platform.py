"""JAX process set-up shared by the entry points: where the persistent
compilation cache lives, the tests' virtual-CPU-device pin, and numeric
env parsing.

The program runs on the backend JAX gives it.  A CPU run is asked for
with the standard ``JAX_PLATFORMS=cpu`` (plus ``JAX_NUM_CPU_DEVICES=N``
for a virtual mesh); nothing here probes for a device or falls back to
another one.
"""

from __future__ import annotations

import os
from pathlib import Path

#: the fixed in-checkout cache location (git-ignored).  The path is part
#: of the cache key, so it must never move between runs.
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    The ONE placement rule, called by ``chip_smoke.py``,
    ``benchmark/run.py``, the CLI and both conftests: where ``JAX_COMPILATION_CACHE_DIR``
    is set the cache stays there (JAX reads the variable itself) and no
    other directory is set in code; otherwise it is
    :data:`COMPILE_CACHE_DIR`.  The compile-time floor is dropped either
    way: the serve grid is many executables that each compile in well
    under JAX's default 1 s threshold — exactly the entries the default
    would skip.
    """
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)


def pin_cpu(n_devices: int = 1) -> None:
    """Pin this process's JAX to ``n_devices`` virtual CPU devices — the
    test suite's mesh.  Works before or after backend init
    (``clear_backends`` repoints an initialized process); ``XLA_FLAGS``
    carries the device count to subprocesses."""
    import re
    flags = os.environ.get("XLA_FLAGS", "")
    want = f"--xla_force_host_platform_device_count={n_devices}"
    if "xla_force_host_platform_device_count" in flags:
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+",
                       want, flags)
        os.environ["XLA_FLAGS"] = flags
    else:
        os.environ["XLA_FLAGS"] = (flags + " " + want).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    from jax.extend.backend import clear_backends
    clear_backends()
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n_devices)
