"""Entry-point JAX set-up (``launch/platform.py``): the compile-cache
placement and the CPU-only device emulation of ``--mesh`` runs."""

import jax
import pytest

from repro.launch import platform

FLAG = "--xla_force_host_platform_device_count"


def test_cpu_device_env_only_on_cpu(monkeypatch):
    """No child (and so no CPU fall-back) unless JAX_PLATFORMS pins the CPU."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert platform.cpu_device_env(8) is None
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    assert platform.cpu_device_env(8) is None


@pytest.mark.parametrize("flags,want", [
    ("", f"{FLAG}=8"),
    ("--xla_dump_to=/x", f"--xla_dump_to=/x {FLAG}=8"),
    (f"{FLAG}=2", f"{FLAG}=2 {FLAG}=8"),
    (f"{FLAG}=8", None),
    (f"{FLAG}=16", None),
])
def test_cpu_device_env_adds_flag_when_short(monkeypatch, flags, want):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS", flags)
    env = platform.cpu_device_env(8)
    assert (None if env is None else env["XLA_FLAGS"]) == want
    if env is not None:
        assert env["JAX_PLATFORMS"] == "cpu"


def test_mesh_devices_refuses_a_mesh_larger_than_the_host():
    n = len(jax.devices())
    assert platform.mesh_devices(n) == jax.devices()
    with pytest.raises(SystemExit, match=f"{n + 1}-device mesh"):
        platform.mesh_devices(n + 1)


def test_compile_cache_dir_env_wins(monkeypatch):
    """A set JAX_COMPILATION_CACHE_DIR is left to JAX; otherwise the cache
    sits at the fixed <checkout>/.jax_cache."""
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", was)
        assert platform.configure_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        got = platform.configure_compile_cache()
        assert got == str(platform.CACHE_DIR)
        assert platform.CACHE_DIR.name == ".jax_cache"
        assert (platform.CACHE_DIR.parent / "pyproject.toml").exists()
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
