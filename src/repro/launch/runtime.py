"""Process set-up shared by the entry points (``launch/train.py``,
``launch/serve.py``, ``chip_smoke.py``): fake host devices, the persistent
compile cache, and the device line every run prints.

Nothing here runs at import time; each entry point calls what it needs.
"""
from __future__ import annotations

import os
from pathlib import Path

# the checkout's root: src/repro/launch/runtime.py -> parents[3]
REPO_ROOT = Path(__file__).resolve().parents[3]
CACHE_DIR = REPO_ROOT / ".jax_cache"


def force_host_devices(n: int, fake_devices: int = 0) -> None:
    """Give the CPU backend ``max(n, fake_devices)`` devices — only when fake
    devices were asked for or the run is pinned to the CPU
    (``JAX_PLATFORMS=cpu``). On an accelerator the mesh uses the real
    devices. Call before JAX initialises its backends."""
    on_cpu = os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip() == "cpu"
    count = max(n, fake_devices)
    if (fake_devices or on_cpu) and count > 1:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={count} "
            + os.environ.get("XLA_FLAGS", "")
        )


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    nothing is set here. Otherwise the cache lives at the fixed
    ``<repo>/.jax_cache`` (git-ignored); the path is part of the cache key,
    so it never depends on a temporary name, a pid or the time."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def device_line() -> str:
    """``platform=<p> kind=<device_kind> count=<n>`` as JAX reports them."""
    import jax

    devs = jax.devices()
    return (f"platform={devs[0].platform} kind={devs[0].device_kind} "
            f"count={len(devs)}")
