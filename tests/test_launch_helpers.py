"""Entry-point helpers: compile-cache placement, forced host devices and
the worker mesh."""
import os

import jax
import pytest

from repro.launch import compile_cache
from repro.launch.mesh import force_host_devices, worker_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_defaults_to_repo_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_compile_cache_env_dir_is_left_to_jax(monkeypatch, tmp_path,
                                              restore_cache_dir):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None   # nothing set


def test_force_host_devices_keeps_other_flags(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "--xla_dump_to=/x "
                       "--xla_force_host_platform_device_count=2")
    force_host_devices(8)
    assert os.environ["XLA_FLAGS"].split() == [
        "--xla_dump_to=/x", "--xla_force_host_platform_device_count=8"]


def test_worker_mesh_falls_back_to_one_worker_per_device():
    n = len(jax.devices())
    mesh = worker_mesh(n + 1, 2)
    assert dict(mesh.shape) == {"data": n, "model": 1}
