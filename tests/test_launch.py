"""Entry-point plumbing: compile-cache placement, the CPU-only forced-device
rehearsal, and ``chip_smoke.py`` refusing to run without a TPU."""
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import pytest

from repro.launch import mesh as mesh_mod
from repro.launch.compile_cache import DEFAULT_CACHE_DIR, configure_compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_compile_cache_honours_the_environment(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        assert configure_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before  # nothing set
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_defaults_to_a_fixed_path_in_the_checkout(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = configure_compile_cache()
        assert got == str(ROOT / ".jax_cache") == str(DEFAULT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == got
        assert configure_compile_cache() == got  # the same on every call
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_forced_host_devices_refuse_an_accelerator_host(monkeypatch):
    """The CPU re-exec must never stand in for real chips."""
    env = mesh_mod.forced_host_devices_env(4)
    assert env["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=4" in env["XLA_FLAGS"]
    monkeypatch.setattr(mesh_mod.jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="CPU rehearsal"):
        mesh_mod.forced_host_devices_env(4)


def _smoke(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("REPRO_AFC_BACKEND", None)
    return subprocess.run(
        [sys.executable, str(script)], cwd=str(cwd), env=env,
        capture_output=True, text=True, timeout=300,
    )


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_fails_without_a_tpu(tmp_path, where):
    """No TPU, or no repository beside the script: a non-zero exit and no
    result line, never a CPU fallback."""
    script = ROOT / "chip_smoke.py"
    if where == "alone":
        script = pathlib.Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    proc = _smoke(script.parent, script)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr
