"""Streaming evaluation paths: bit-for-bit equality with the batched ones.

The streaming engine (``evaluate_mask_stream``), the streamed counter-mask
``run_sweep`` path, and ``monte_carlo_replay(engine="streamed")`` must all
reproduce the batched grids exactly -- for any chunking, including chunk
sizes of 1 and larger than the whole stream.
"""

import os
import subprocess
import sys
import tracemalloc

import numpy as np
import pytest

from repro.churn.monte_carlo import ChurnSpec, monte_carlo_replay
from repro.core.prng import counter_fault_masks
from repro.sim.engine import (evaluate_mask_stream, evaluate_masks,
                              run_sweep)
from repro.sim.scenario import CounterIIDSnapshots, ScenarioSpec

ARCHES = ("infinitehbd-k3", "nvl-72")


def _spec(samples, num_nodes=720, ratio=0.07, seed=3):
    return ScenarioSpec(num_nodes=num_nodes,
                        snapshots=CounterIIDSnapshots(ratio, samples, seed),
                        tp_sizes=(16, 64), architectures=ARCHES)


def _split(masks, sizes):
    out, lo = [], 0
    for s in sizes:
        out.append(masks[lo:lo + s])
        lo += s
    assert lo == masks.shape[0]
    return out


@pytest.mark.parametrize("chunk_snapshots", [1, 7, 64, 10_000])
def test_stream_matches_batched_any_chunking(chunk_snapshots):
    spec = _spec(97)
    models = spec.models()
    masks = spec.snapshots.masks(spec.num_nodes)
    ref = evaluate_masks(models, spec.tp_sizes, masks, backend="numpy")
    # ragged source chunks deliberately misaligned with evaluation blocks
    chunks = _split(masks, [1, 30, 0, 2, 50, 14])
    got = evaluate_mask_stream(models, spec.tp_sizes, chunks, 97,
                               chunk_snapshots=chunk_snapshots,
                               backend="numpy")
    for g, r in zip(got[:3], ref[:3]):
        assert np.array_equal(g, r)


def test_stream_length_mismatch_raises():
    spec = _spec(8)
    models = spec.models()
    masks = spec.snapshots.masks(spec.num_nodes)
    with pytest.raises(ValueError, match="yielded 8"):
        evaluate_mask_stream(models, spec.tp_sizes, [masks], 9,
                             backend="numpy")


def test_stream_empty():
    spec = _spec(4)
    models = spec.models()
    total, faulty, placed, _ = evaluate_mask_stream(
        models, spec.tp_sizes, [], 0, backend="numpy")
    ref = evaluate_masks(models, spec.tp_sizes,
                         np.zeros((0, spec.num_nodes), bool),
                         backend="numpy")
    assert np.array_equal(total, ref[0])
    assert faulty.shape == (2, 0, 2) and placed.shape == (2, 0, 2)


def test_run_sweep_streams_counter_masks():
    """The counter-mask run_sweep path (which now never materializes the
    full matrix) equals evaluating a pre-materialized matrix."""
    spec = _spec(61)
    ref = run_sweep(spec, masks=spec.snapshots.masks(spec.num_nodes),
                    backend="numpy")
    for chunk in (1, 16, 1000):
        got = run_sweep(spec, chunk_snapshots=chunk, backend="numpy")
        assert np.array_equal(got.total_gpus, ref.total_gpus)
        assert np.array_equal(got.faulty_gpus, ref.faulty_gpus), chunk
        assert np.array_equal(got.placed_gpus, ref.placed_gpus), chunk


def test_counter_mask_start_offset_is_the_stream():
    full = counter_fault_masks(640, 0.1, 40, seed=5)
    parts = [counter_fault_masks(640, 0.1, n, seed=5, start=lo)
             for lo, n in [(0, 13), (13, 1), (14, 26)]]
    assert np.array_equal(np.concatenate(parts), full)


# the three realizations have 226, 263 and 235 intervals: blocks smaller
# than one realization (37, 100), one realization long (226) and longer
# than one (300) all straddle realization boundaries
@pytest.mark.parametrize("chunk_snapshots", [1, 37, 100, 226, 300, 100_000])
def test_monte_carlo_streamed_matches_batched(chunk_snapshots):
    spec = ChurnSpec(trace_nodes=60, horizon_h=24.0 * 30, tp_sizes=(16, 32),
                     architectures=ARCHES, seed=7)
    ref = monte_carlo_replay(spec, 3, engine="batched", backend="numpy")
    got = monte_carlo_replay(spec, 3, engine="streamed", backend="numpy",
                             chunk_snapshots=chunk_snapshots)
    assert got.num_traces == ref.num_traces == 3
    for tg, tr in zip(got.timelines, ref.timelines):
        assert np.array_equal(tg.edges_h, tr.edges_h)
        assert np.array_equal(tg.total_gpus, tr.total_gpus)
        assert np.array_equal(tg.faulty_gpus, tr.faulty_gpus)
        assert np.array_equal(tg.placed_gpus, tr.placed_gpus)
    assert np.array_equal(got.integrated_waste(), ref.integrated_waste())


def test_monte_carlo_streamed_peak_memory_is_a_block():
    """A long realization streams in a small multiple of one mask block:
    the whole (intervals, nodes) matrix and its int16 counts never exist."""
    spec = ChurnSpec(trace_nodes=1024, horizon_h=24.0 * 120,
                     tp_sizes=(32,), architectures=("big-switch",), seed=11)
    traces = [spec.trace(0)]
    intervals = len(traces[0].interval_edges())
    block = 64
    whole = intervals * spec.num_nodes * 3          # bool masks + int16
    one_block = block * spec.num_nodes * 3
    tracemalloc.start()
    try:
        got = monte_carlo_replay(spec, traces, engine="streamed",
                                 backend="numpy", chunk_snapshots=block)
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert got.timelines[0].num_intervals == intervals > 40 * block
    # the grids and timelines themselves take (intervals x 8 B) per array
    assert peak < 8 * one_block + 64 * intervals
    assert peak < whole / 10


def test_monte_carlo_streamed_empty():
    spec = ChurnSpec(trace_nodes=40, tp_sizes=(16,), architectures=ARCHES)
    got = monte_carlo_replay(spec, 0, engine="streamed", backend="numpy")
    assert got.num_traces == 0


def test_monte_carlo_rejects_unknown_engine():
    spec = ChurnSpec(trace_nodes=40, architectures=ARCHES)
    with pytest.raises(ValueError, match="streamed"):
        monte_carlo_replay(spec, 1, engine="bogus")


def test_stream_jax_backend_matches_numpy():
    pytest.importorskip("jax")
    spec = _spec(45, num_nodes=144)
    models = spec.models()
    masks = spec.snapshots.masks(spec.num_nodes)
    ref = evaluate_masks(models, spec.tp_sizes, masks, backend="numpy")
    got = evaluate_mask_stream(models, spec.tp_sizes,
                               _split(masks, [10, 1, 34]), 45,
                               chunk_snapshots=8, backend="jax")
    assert got[3] == "jax"
    for g, r in zip(got[:3], ref[:3]):
        assert np.array_equal(g, r)


def test_stream_jax_backend_runs_one_compiled_shape():
    """Streams whose last blocks differ in length pad them up to the block:
    after one stream has compiled the block, no other stream of that block
    size compiles anything, whatever its length."""
    pytest.importorskip("jax")
    from jax import monitoring
    events = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")
    compiles = []

    def listen(event, duration, **_):
        if event in events:
            compiles.append(event)

    spec = _spec(61, num_nodes=144, seed=5)
    models = spec.models()
    masks = spec.snapshots.masks(spec.num_nodes)
    evaluate_mask_stream(models, spec.tp_sizes, [masks[:16]], 16,
                         chunk_snapshots=8, backend="jax")
    monitoring.register_event_duration_secs_listener(listen)
    try:
        # tails of 5 and 3 rows, source chunks straddling the blocks
        for total, sizes in ((61, [13, 30, 18]), (43, [10, 1, 32])):
            ref = evaluate_masks(models, spec.tp_sizes, masks[:total],
                                 backend="numpy")
            got = evaluate_mask_stream(models, spec.tp_sizes,
                                       _split(masks[:total], sizes), total,
                                       chunk_snapshots=8, backend="jax")
            for g, r in zip(got[:3], ref[:3]):
                assert np.array_equal(g, r)
    finally:
        monitoring.unregister_event_duration_listener(listen)
    assert compiles == []


@pytest.mark.slow
def test_stream_sharded_subprocess():
    """Streaming equality under forced 8-device sharding (subprocess so the
    XLA device-count flag applies before jax initializes)."""
    pytest.importorskip("jax")
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "REPRO_SWEEP_BACKEND")}
    script = os.path.join(os.path.dirname(__file__),
                          "_stream_sharded_check.py")
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env["PYTHONPATH"] = os.path.abspath(src)
    proc = subprocess.run([sys.executable, script], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr
    assert "OK stream_sharded" in proc.stdout
