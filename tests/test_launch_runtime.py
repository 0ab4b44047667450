"""Process set-up shared by the entry points (repro.launch.runtime): where
the persistent compile cache lives, and when fake host devices are made."""
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.launch import runtime

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.fixture
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defaults_to_repo_dir(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = runtime.enable_compile_cache()
    assert path == str(runtime.REPO_ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert (runtime.REPO_ROOT / "pyproject.toml").exists()


def test_compile_cache_env_wins(monkeypatch, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert runtime.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before  # JAX reads the env


def test_compile_cache_entries_land_in_env_dir(tmp_path):
    """End to end in a fresh process: with JAX_COMPILATION_CACHE_DIR set, a
    compile writes its entry there."""
    script = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.launch import runtime
        runtime.enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: x * 2 + 1)(jnp.arange(8.0)).block_until_ready()
    """)
    env = {**os.environ, "PYTHONPATH": _SRC, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path)}
    subprocess.run([sys.executable, "-c", script], env=env, check=True,
                   timeout=120)
    assert any(tmp_path.iterdir())


@pytest.mark.parametrize("platforms,fake,expect", [
    ("", 0, None),      # an accelerator run: the real devices, no flag
    ("", 8, 8),         # fake devices asked for
    ("cpu", 0, 4),      # pinned to the CPU: the mesh size
    ("cpu", 8, 8),
    ("tpu", 0, None),
])
def test_force_host_devices_only_on_cpu_or_fake(monkeypatch, platforms, fake,
                                                expect):
    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    monkeypatch.setenv("XLA_FLAGS", "--xla_dump_to=none")
    runtime.force_host_devices(4, fake)
    flags = os.environ["XLA_FLAGS"]
    if expect is None:
        assert flags == "--xla_dump_to=none"
    else:
        assert flags.startswith(f"--xla_force_host_platform_device_count={expect} ")
