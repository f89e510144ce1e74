"""JAX-backend equivalence suite: ``run_sweep(backend="jax")`` must be
bit-for-bit equal (int64 grids) to the NumPy engine.

Covers every registered architecture, awkward TP sizes, empty-snapshot and
all-faulty edge cases, chunk-boundary invariance, the counter-threefry mask
stream drawn on the device against its NumPy original (and that stream
against ``jax.random``'s raw primitives), and (slow tier, subprocess)
forced 8-device sharding.
"""

import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro import obs
from repro.core.prng import (counter_fault_masks, ratio_threshold,
                             threefry2x32, threefry_bits, threefry_fold_in,
                             threefry_fold_in_batch, threefry_seed)
from repro.sim import (CounterIIDSnapshots, DEFAULT_ARCHITECTURES,
                       IIDSnapshots, ScenarioSpec, TraceSnapshots,
                       resolve_backend, run_sweep)

jax = pytest.importorskip("jax")

ROOT = Path(__file__).resolve().parents[1]


def _assert_grids_equal(a, b):
    assert a.names == b.names
    assert a.total_gpus.dtype == b.total_gpus.dtype == np.int64
    assert a.placed_gpus.dtype == b.placed_gpus.dtype == np.int64
    assert np.array_equal(a.total_gpus, b.total_gpus)
    assert np.array_equal(a.faulty_gpus, b.faulty_gpus)
    assert np.array_equal(a.placed_gpus, b.placed_gpus)


# ----------------------------------------------------- backend equivalence

@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("num_nodes", [97, 300])
def test_jax_matches_numpy_all_architectures(seed, num_nodes):
    spec = ScenarioSpec(num_nodes=num_nodes,
                        snapshots=IIDSnapshots(0.04 + 0.05 * seed,
                                               samples=16, seed=seed),
                        tp_sizes=(4, 8, 24, 32, 48, 128),
                        architectures=DEFAULT_ARCHITECTURES)
    ref = run_sweep(spec, backend="numpy")
    got = run_sweep(spec, backend="jax")
    assert ref.backend == "numpy" and got.backend == "jax"
    _assert_grids_equal(ref, got)


def test_jax_matches_numpy_trace_snapshots():
    spec = ScenarioSpec(num_nodes=240,
                        snapshots=TraceSnapshots(trace_nodes=130, samples=40,
                                                 seed=2),
                        tp_sizes=(16, 32))
    _assert_grids_equal(run_sweep(spec, backend="numpy"),
                        run_sweep(spec, backend="jax"))


def test_jax_chunking_invariance():
    spec = ScenarioSpec(num_nodes=144,
                        snapshots=IIDSnapshots(0.08, samples=41, seed=7),
                        tp_sizes=(8, 32))
    ref = run_sweep(spec, backend="jax", chunk_snapshots=4096)
    for chunk in (1, 7, 41):
        _assert_grids_equal(ref, run_sweep(spec, backend="jax",
                                           chunk_snapshots=chunk))


def test_jax_empty_snapshots():
    spec = ScenarioSpec(num_nodes=64,
                        snapshots=IIDSnapshots(0.1, samples=0),
                        tp_sizes=(16, 32))
    ref = run_sweep(spec, backend="numpy")
    got = run_sweep(spec, backend="jax")
    assert got.placed_gpus.shape == ref.placed_gpus.shape
    _assert_grids_equal(ref, got)


def test_jax_extreme_masks():
    n = 64
    masks = np.stack([np.zeros(n, bool), np.ones(n, bool),
                      np.arange(n) < 62,          # only a tail sliver healthy
                      ~(np.arange(n) < 2)])       # only a head sliver healthy
    spec = ScenarioSpec(num_nodes=n, snapshots=None, tp_sizes=(16, 32))
    _assert_grids_equal(run_sweep(spec, masks=masks, backend="numpy"),
                        run_sweep(spec, masks=masks, backend="jax"))


def test_jax_mask_width_clipping():
    """Masks wider and narrower than the cluster follow _clip_masks."""
    spec = ScenarioSpec(num_nodes=100, snapshots=None, tp_sizes=(16,))
    rng = np.random.default_rng(0)
    for width in (60, 100, 140):
        masks = rng.random((9, width)) < 0.2
        _assert_grids_equal(run_sweep(spec, masks=masks, backend="numpy"),
                            run_sweep(spec, masks=masks, backend="jax"))


# ------------------------------------------------ counter masks on device

@pytest.mark.parametrize("seed", [0, 11, 2**32 + 5, 5_200_000_000])
def test_threefry2x32_jnp_matches_numpy_on_array_keys(seed):
    """The jnp cipher with one key per row (host arrays and traced), seeds
    past 32 bits included, equals the NumPy cipher row by row."""
    from repro.core.prng import counter_lanes
    from repro.faults.jax_mirror import threefry2x32_jnp
    keys = threefry_fold_in_batch(threefry_seed(seed), np.arange(9) + 3)
    c0, c1 = counter_lanes(101)
    want = [threefry2x32(k[0], k[1], c0, c1) for k in keys]
    host = threefry2x32_jnp(keys[:, :1], keys[:, 1:], c0, c1)
    traced = jax.jit(lambda k: threefry2x32_jnp(k[:, :1], k[:, 1:], c0,
                                                c1))(keys)
    for got in (host, traced):
        assert np.array_equal(np.asarray(got[0]), np.stack([w[0] for w in want]))
        assert np.array_equal(np.asarray(got[1]), np.stack([w[1] for w in want]))


@pytest.mark.parametrize("num_nodes", [97, 64])
@pytest.mark.parametrize("start", [0, 1_000_003])
def test_counter_masks_jax_matches_numpy_mirror(num_nodes, start):
    from repro.sim.jax_backend import device_masks
    from repro.sim.sources import MaskGen
    for ratio, seed in ((0.07, 0), (0.5, 11), (0.0, 3), (1.0, 5),
                        (0.07, 5_200_000_000)):
        gen = MaskGen(samples=13, num_nodes=num_nodes, fault_ratio=ratio,
                      seed=seed, start=start)
        [dev] = device_masks(gen, 13)
        host = counter_fault_masks(num_nodes, ratio, 13, seed, start=start)
        assert np.array_equal(dev, host), (ratio, seed)


def test_counter_specs_of_one_shape_share_one_executable(tel):
    """Seed and ratio are arguments of the device programs, not part of
    their cache keys: a second spec that differs in both compiles
    nothing."""
    from repro.sim import jax_backend

    def spec(ratio, seed):
        return ScenarioSpec(num_nodes=203,
                            snapshots=CounterIIDSnapshots(ratio, samples=24,
                                                          seed=seed),
                            tp_sizes=(16, 32),
                            architectures=("infinitehbd-k3", "nvl-72"))

    first = run_sweep(spec(0.05, 1), backend="jax")
    second = run_sweep(spec(0.12, 2**33 + 9), backend="jax")
    assert obs.summary()["counters"]["sim.jax.jit_cache_miss"] == 1
    [draw] = [fn for (width, mesh), fn in jax_backend._DRAW_CACHE.items()
              if width == 203 and mesh is None]
    [grid] = [fn for key, fn in jax_backend._GRID_CACHE.items()
              if key[2] == 203 and key[3] is None]
    assert draw._cache_size() == grid._cache_size() == 1
    assert not np.array_equal(first.placed_gpus, second.placed_gpus)


def test_counter_mirror_matches_jax_random_primitives():
    """The NumPy threefry mirror reproduces jax.random's raw stream."""
    import jax.numpy as jnp
    key = jax.random.PRNGKey(123, impl="threefry2x32")
    k_np = threefry_seed(123)
    assert np.array_equal(np.asarray(jax.random.key_data(key)), k_np)
    kf = jax.random.fold_in(key, 42)
    kf_np = threefry_fold_in(k_np, 42)
    assert np.array_equal(np.asarray(jax.random.key_data(kf)), kf_np)
    for n in (1, 6, 7, 720):
        got = threefry_bits(kf_np, n,
                            bool(jax.config.jax_threefry_partitionable))
        ref = np.asarray(jax.random.bits(kf, (n,), jnp.uint32))
        assert np.array_equal(got, ref), n


def test_counter_spec_cross_backend_device_generation(tel):
    """The jax backend draws every block of a counter spec on the device,
    the short tail block included (no host matrix, no host draw), and
    still matches the NumPy engine bit-for-bit."""
    spec = ScenarioSpec(num_nodes=210,
                        snapshots=CounterIIDSnapshots(0.09, samples=37,
                                                      seed=6),
                        tp_sizes=(16, 32, 48))
    ref = run_sweep(spec, backend="numpy")
    for chunk in (1, 10, 1000):
        obs.reset()
        got = run_sweep(spec, backend="jax", chunk_snapshots=chunk)
        _assert_grids_equal(ref, got)
        rows = [min(chunk, 37 - lo) for lo in range(0, 37, chunk)]
        assert [r.attrs["rows"] for r in tel.spans
                if r.name == "sim.jax.eval_block"] == rows
        assert [r.attrs for r in tel.spans
                if r.name == "prng.device_masks"] == \
            [{"samples": n, "nodes": 210} for n in rows]
        counters = obs.summary()["counters"]
        assert counters["prng.device_masks_drawn"] == 37
        assert "prng.masks_generated" not in counters


def test_counter_masks_row_depends_only_on_seed_and_index():
    a = counter_fault_masks(80, 0.1, 10, seed=1)
    b = counter_fault_masks(80, 0.1, 4, seed=1)
    assert np.array_equal(a[:4], b)


def test_ratio_threshold_bounds():
    assert ratio_threshold(0.0) == 0
    assert ratio_threshold(1.0) == 1 << 32
    assert 0 < ratio_threshold(0.5) < 1 << 32


# -------------------------------------------------------- backend selection

def test_resolve_backend_explicit_and_env(monkeypatch):
    spec = ScenarioSpec(num_nodes=32, snapshots=IIDSnapshots(0.1, samples=2),
                        tp_sizes=(16,))
    models = spec.models()
    monkeypatch.delenv("REPRO_SWEEP_BACKEND", raising=False)
    assert resolve_backend("auto", models) == "jax"     # jax is installed
    assert resolve_backend("numpy", models) == "numpy"
    assert resolve_backend("jax", models) == "jax"
    monkeypatch.setenv("REPRO_SWEEP_BACKEND", "numpy")
    assert resolve_backend("auto", models) == "numpy"
    assert resolve_backend(None, models) == "numpy"
    assert resolve_backend("jax", models) == "jax"      # explicit wins
    monkeypatch.setenv("REPRO_SWEEP_BACKEND", "jax")
    assert resolve_backend("auto", models) == "jax"
    monkeypatch.setenv("REPRO_SWEEP_BACKEND", "bogus")
    with pytest.raises(ValueError):
        resolve_backend("auto", models)
    with pytest.raises(ValueError):
        resolve_backend("cuda", models)


def test_explicit_jax_backend_rejects_unknown_model():
    from repro.core.hbd_models import HBDModel
    from repro.sim import jax_backend

    class WeirdModel(HBDModel):
        name = "weird"

    models = [WeirdModel(16, 4)]
    assert not jax_backend.available_for(models)
    assert resolve_backend("auto", models) == "numpy"   # silent fallback
    with pytest.raises(RuntimeError, match="weird"):
        resolve_backend("jax", models)


# ------------------------------------------------- forced 8-device sharding

@pytest.mark.slow
def test_jax_backend_under_forced_sharding():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env.pop("XLA_FLAGS", None)
    env.pop("REPRO_SWEEP_BACKEND", None)
    res = subprocess.run(
        [sys.executable, str(ROOT / "tests" / "_jax_backend_sharded_check.py")],
        capture_output=True, text=True, env=env, timeout=900)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "OK jax_backend_sharded" in res.stdout
