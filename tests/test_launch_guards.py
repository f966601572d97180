"""Start-up guards of the entry points: where the compilation cache
goes, which platform the dry-run may touch, that a profiled run never
goes on without its profiler, that the simulated sharded benchmark
refuses a TPU host, and that meshes carry Auto axes."""
import argparse
import os

import jax
import pytest
from jax.sharding import AxisType


@pytest.fixture
def cache_dir_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_env_wins(monkeypatch, cache_dir_config, tmp_path):
    from repro.launch.compile_cache import init_compile_cache
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert init_compile_cache() == str(tmp_path)
    # left alone: JAX itself reads the variable at start-up
    assert jax.config.jax_compilation_cache_dir is None


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch,
                                                    cache_dir_config):
    from repro.launch import compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.init_compile_cache()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(root, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.init_compile_cache() == path      # stable


def test_dryrun_pins_cpu(monkeypatch):
    from repro.launch.dryrun import configure_xla
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setenv("XLA_FLAGS", "")
    configure_xla(argparse.Namespace(mesh="pod", serve=True, all=False,
                                     shape="decode_32k", sync="baseline"))
    assert os.environ["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=512" \
        in os.environ["XLA_FLAGS"]


@pytest.fixture
def broken_profiler(monkeypatch):
    def refuse(*a, **k):
        raise RuntimeError("profiler unavailable")
    from jax._src import profiler as _profiler   # what trace() calls
    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    monkeypatch.setattr(_profiler, "start_trace", refuse)


def test_profiled_obs_raises_when_profiler_fails(tmp_path,
                                                 broken_profiler):
    from repro.obs.sink import make_obs
    with pytest.raises(RuntimeError, match="profiler unavailable"):
        make_obs(str(tmp_path), profile=True)


def test_profiler_trace_raises_when_profiler_fails(tmp_path,
                                                   broken_profiler):
    from repro.obs.trace import profiler_trace
    with pytest.raises(RuntimeError, match="profiler unavailable"):
        with profiler_trace(str(tmp_path)):
            pass


def test_sharded_serving_bench_refuses_tpu(monkeypatch):
    from benchmarks import serving_bench
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="chip_smoke.py --chips 4"):
        serving_bench.run_sharded()


@pytest.mark.parametrize("spec", ["host", "data", "1x1"])
def test_serve_mesh_axes_are_auto(spec):
    from repro.launch.mesh import make_serve_mesh
    mesh = make_serve_mesh(spec)
    assert mesh.axis_names == ("data", "model")
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)
