"""``repro.runtime``: the one mesh constructor, the engines' device choice
and the compile-cache location."""

import jax
import pytest

from repro import runtime
from repro.dcn import jax_backend as dcn_jax
from repro.sim import jax_backend as sim_jax


def test_make_mesh_axes_are_auto():
    mesh = runtime.make_mesh((1,), ("snap",))
    assert mesh.axis_names == ("snap",)
    assert mesh.axis_types == (jax.sharding.AxisType.Auto,)


def test_use_devices_restricts_both_engines():
    every = len(jax.devices())
    assert sim_jax.num_devices() == dcn_jax.num_devices() == every
    with runtime.use_devices(1):
        assert runtime.engine_devices() == jax.devices()[:1]
        assert sim_jax.num_devices() == dcn_jax.num_devices() == 1
        assert runtime.snapshot_mesh("snap") is None
    assert runtime.engine_devices() == jax.devices()


@pytest.mark.parametrize("count", [0, 10_000])
def test_use_devices_rejects_impossible_counts(count):
    with pytest.raises((ValueError, RuntimeError)):
        with runtime.use_devices(count):
            runtime.engine_devices()


@pytest.fixture
def cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_follows_the_environment(cache_config, monkeypatch,
                                               tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    before = jax.config.jax_compilation_cache_dir
    assert runtime.enable_compile_cache(tmp_path) == str(tmp_path / "env")
    assert jax.config.jax_compilation_cache_dir == before   # JAX reads it


def test_compile_cache_has_a_fixed_path_in_the_root(cache_config,
                                                    monkeypatch, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = runtime.enable_compile_cache(tmp_path)
    assert path == str(tmp_path.resolve() / runtime.CACHE_DIRNAME)
    assert jax.config.jax_compilation_cache_dir == path
    assert runtime.enable_compile_cache(tmp_path) == path
