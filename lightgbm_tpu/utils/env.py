"""Process-environment helpers: the forced-CPU subprocess environment and
the persistent compile cache's location.

Tests and verification run on the CPU backend (`JAX_PLATFORMS=cpu`, eight
virtual devices via `XLA_FLAGS`); the chip is reached only by sending a
command through the chip tool, one process per chip.
"""
from __future__ import annotations

import os
import re

#: the checkout root (the directory that holds the `lightgbm_tpu` package)
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cleaned_cpu_env(base_env: dict, n_devices: int = 8) -> dict:
    """A copy of `base_env` for a subprocess that must run on a pure CPU
    backend with exactly `n_devices` virtual devices."""
    env = dict(base_env)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    # replace (not keep) any existing device-count flag: a stale value from
    # another harness would silently under-provision the mesh
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "", flags)
    env["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n_devices}"
    ).strip()
    return env


def setup_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory.

    Called once by each entry point (`chip_smoke.py`, `bench.py`,
    `lightgbm_tpu/cli.py:run`) before the first compile.  Where
    `JAX_COMPILATION_CACHE_DIR` is set JAX reads it itself and nothing is
    set here; otherwise the cache lives at `<checkout>/.jax_cache` — a
    FIXED path, because the path is part of the cache key and a directory
    that moves never hits.  There every program is cached, however quick
    its compile: a program whose compile time straddles JAX's default
    one-second threshold would otherwise be written on some runs only."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
