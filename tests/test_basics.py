"""Basics: init/shutdown/topology queries (reference test/parallel pattern:
rank/size sanity; here single-controller over 8 virtual devices)."""

import jax
import numpy as np
import pytest

import horovod_tpu as hvd


def test_init_idempotent():
    hvd.init()
    assert hvd.is_initialized()
    hvd.init()  # second call is a no-op
    assert hvd.is_initialized()


def test_topology_single_controller():
    hvd.init()
    assert hvd.size() == jax.device_count() == 8
    assert hvd.rank() == 0
    assert hvd.local_size() == 8
    assert hvd.local_rank() == 0
    assert hvd.cross_size() == 1
    assert hvd.cross_rank() == 0
    assert hvd.process_count() == 1


def test_not_initialized_raises():
    with pytest.raises(hvd.NotInitializedError):
        hvd.rank()


def test_mesh_created():
    hvd.init()
    mesh = hvd.mesh()
    assert mesh.axis_names == ("data",)
    assert mesh.devices.size == 8


def test_env_rank_override(monkeypatch):
    monkeypatch.setenv("HOROVOD_RANK", "3")
    monkeypatch.setenv("HOROVOD_SIZE", "16")
    monkeypatch.setenv("HOROVOD_LOCAL_RANK", "1")
    monkeypatch.setenv("HOROVOD_LOCAL_SIZE", "4")
    monkeypatch.setenv("HOROVOD_CROSS_RANK", "0")
    monkeypatch.setenv("HOROVOD_CROSS_SIZE", "4")
    hvd.init(use_controller=False)
    assert hvd.rank() == 3
    assert hvd.size() == 16
    assert hvd.local_rank() == 1
    assert hvd.local_size() == 4
    assert hvd.cross_size() == 4


def test_init_rejects_rank_permuted_jax_world(monkeypatch):
    """Env-provided ranks must match an existing jax.distributed world's
    process ids: device-plane collectives place shards in process-index
    order but read them back in rank order, so a permuted world silently
    misroutes broadcast roots / gather order.  init() is the synchronous
    fail-fast point (every rank passes through it before any collective)."""
    from jax._src import distributed as _jd

    monkeypatch.setenv("HOROVOD_RANK", "1")
    monkeypatch.setenv("HOROVOD_SIZE", "2")
    monkeypatch.setattr(_jd.global_state, "client", object())
    monkeypatch.setattr(_jd.global_state, "process_id", 0)
    monkeypatch.setattr(_jd.global_state, "num_processes", 2)
    with pytest.raises(RuntimeError, match="process_id 0 != rank 1"):
        hvd.init(use_controller=False)
    assert not hvd.is_initialized()

    # Aligned world initializes fine.
    monkeypatch.setattr(_jd.global_state, "process_id", 1)
    hvd.init(use_controller=False)
    assert hvd.rank() == 1


def test_shutdown_resets():
    hvd.init()
    hvd.shutdown()
    assert not hvd.is_initialized()


def test_custom_mesh_axes(monkeypatch):
    monkeypatch.setenv("HVD_TPU_MESH_AXES", "data:4,model:2")
    hvd.init()
    mesh = hvd.mesh()
    assert mesh.axis_names == ("data", "model")
    assert mesh.devices.shape == (4, 2)


def _cache_dir_after_init(tmp_path, **env):
    """jax's compilation-cache directory after hvd.init(), in a fresh
    process (the config is process-global and pytest's own is set)."""
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(env, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, %r); import jax; "
         "import horovod_tpu as hvd; hvd.init(); "
         "print(jax.config.jax_compilation_cache_dir)" % repo],
        capture_output=True, text=True, timeout=120, env=full,
        cwd=str(tmp_path))
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.strip().splitlines()[-1]


@pytest.mark.timeout(300)
def test_compile_cache_is_placed_from_outside_or_fixed(tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins untouched; without it every process
    resolves the same directory inside the checkout (the path is part of
    the cache key, so one that moves never hits)."""
    import os
    outside = str(tmp_path / "cache")
    assert _cache_dir_after_init(
        tmp_path, JAX_COMPILATION_CACHE_DIR=outside) == outside
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    first = _cache_dir_after_init(tmp_path)
    second = _cache_dir_after_init(tmp_path)
    assert first == second == os.path.join(repo, ".jax_cache")
