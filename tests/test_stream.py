"""Streaming evaluation paths: bit-for-bit equality with the batched ones.

The engine's one loop over a mask source (``evaluate_source``) behind
``evaluate_mask_stream``, the streamed counter-mask ``run_sweep`` path and
``monte_carlo_replay(engine="streamed")`` must all reproduce the batched
grids exactly -- for any chunking, including chunk sizes of 1 and larger
than the whole stream.
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
                              evaluate_source, run_sweep)
from repro.sim.scenario import CounterIIDSnapshots, ScenarioSpec
from repro.sim.sources import HostChunks, IntervalDeltas, MaskGen

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


def _stream(kind, seed, length):
    """A mask source of ``kind`` over 144 nodes and the masks it streams:
    ``length`` counter snapshots in ragged host chunks or drawn on the
    device, or two realizations of ``length`` hours of the churn trace
    as interval deltas, four to a chunk (most blocks take several
    chunks)."""
    if kind == "interval":
        spec = ChurnSpec(trace_nodes=72, horizon_h=float(length), seed=seed)
        pieces = [(tr, tr.interval_edges())
                  for tr in (spec.trace(0), spec.trace(1))]
        masks = np.concatenate([tr.fault_masks(ts) for tr, ts in pieces])
        return IntervalDeltas(pieces, 144, capacity=4), masks
    masks = counter_fault_masks(144, 0.07, length, seed)
    if kind == "counter":
        return MaskGen(length, 144, 0.07, seed), masks
    third = length // 3
    return HostChunks(_split(masks, [third, 1, length - third - 1])), masks


@pytest.mark.parametrize("kind", ["host", "counter", "interval"])
def test_stream_jax_backend_runs_one_compiled_shape(kind):
    """Streams whose last blocks differ in length pad them up to the block:
    after one stream of a source has compiled the block, no other stream
    of that source and block size compiles anything, whatever its length
    -- host chunks straddling the blocks, counter masks drawn on the
    device and interval masks built on the device alike."""
    pytest.importorskip("jax")
    from jax import monitoring
    events = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")
    compiles = []

    def listen(event, duration, **_):
        if event in events:
            compiles.append(event)

    models = _spec(1, num_nodes=144).models()

    def run(seed, length):
        source, masks = _stream(kind, seed, length)
        ref = evaluate_masks(models, (16, 64), masks, backend="numpy")
        got = evaluate_source(models, (16, 64), source, masks.shape[0],
                              chunk_snapshots=8, backend="jax")
        assert got[3] == "jax"
        for g, r in zip(got[:3], ref[:3]):
            assert np.array_equal(g, r)
        return masks.shape[0]

    assert run(1, 40) > 8
    monitoring.register_event_duration_secs_listener(listen)
    try:
        tails = [run(seed, length) % 8 for seed, length in ((5, 61), (7, 43))]
    finally:
        monitoring.unregister_event_duration_listener(listen)
    assert compiles == []
    assert all(tails) and len(set(tails)) == 2


# the same three realizations as above, their masks built on the device
@pytest.mark.parametrize("chunk_snapshots", [1, 37, 100, 226, 300, 100_000])
def test_monte_carlo_streamed_jax_builds_the_masks(chunk_snapshots):
    """``engine="streamed"`` on the JAX backend builds the interval masks
    on the device from the stream's deltas: the same grids as the NumPy
    streamed and batched engines."""
    pytest.importorskip("jax")
    spec = ChurnSpec(trace_nodes=60, horizon_h=24.0 * 30, tp_sizes=(16, 32),
                     architectures=ARCHES, seed=7)
    ref = monte_carlo_replay(spec, 3, engine="batched", backend="numpy")
    host = monte_carlo_replay(spec, 3, engine="streamed", backend="numpy",
                              chunk_snapshots=chunk_snapshots)
    got = monte_carlo_replay(spec, 3, engine="streamed", backend="jax",
                             chunk_snapshots=chunk_snapshots)
    assert got.backend == "jax" and got.num_traces == 3
    for tg, th, tr in zip(got.timelines, host.timelines, ref.timelines):
        assert np.array_equal(tg.edges_h, tr.edges_h)
        for name in ("total_gpus", "faulty_gpus", "placed_gpus"):
            assert np.array_equal(getattr(tg, name), getattr(tr, name))
            assert np.array_equal(getattr(th, name), getattr(tr, name))


def test_monte_carlo_streamed_jax_empty_and_short():
    pytest.importorskip("jax")
    spec = ChurnSpec(trace_nodes=40, horizon_h=24.0, tp_sizes=(16,),
                     architectures=ARCHES, seed=1)
    assert monte_carlo_replay(spec, 0, engine="streamed",
                              backend="jax").num_traces == 0
    ref = monte_carlo_replay(spec, 1, engine="batched", backend="numpy")
    got = monte_carlo_replay(spec, 1, engine="streamed", backend="jax",
                             chunk_snapshots=4096)
    assert np.array_equal(got.timelines[0].placed_gpus,
                          ref.timelines[0].placed_gpus)


def test_delta_stream_length_mismatch_raises():
    pytest.importorskip("jax")
    spec = ChurnSpec(trace_nodes=20, horizon_h=48.0, tp_sizes=(16,),
                     architectures=ARCHES, seed=2)
    tr = spec.trace(0)
    edges = tr.interval_edges()
    with pytest.raises(ValueError, match=f"yielded {len(edges)}"):
        evaluate_source(spec.models(), spec.tp_sizes,
                        IntervalDeltas([(tr, edges)], tr.num_nodes),
                        len(edges) + 1, chunk_snapshots=8, backend="jax")


def _end_inclusive(monkeypatch):
    """Every fault also covers the sample at its end time."""
    from repro.core.trace import FaultEvent, FaultTrace
    real = FaultTrace.interval_deltas

    def deltas(self, ts):
        later = [FaultEvent(e.node, e.start_h, np.nextafter(e.end_h, np.inf))
                 for e in self.events]
        return real(FaultTrace(self.num_nodes, self.horizon_h, later), ts)
    monkeypatch.setattr(FaultTrace, "interval_deltas", deltas)


def _carry_dropped(monkeypatch):
    """Every block starts from no active events."""
    import jax.numpy as jnp
    from repro.sim.jax_backend import IntervalMaskSource
    real = IntervalMaskSource.build

    def build(self, block, chunks):
        self.carry = jnp.zeros_like(self.carry)
        return real(self, block, chunks)
    monkeypatch.setattr(IntervalMaskSource, "build", build)


@pytest.mark.parametrize("plant", [_end_inclusive, _carry_dropped])
def test_planted_device_recipe_fault_changes_the_grids(plant, monkeypatch):
    """The equalities above would see a wrong device recipe: an end
    treated as inclusive, or the counts dropped between blocks."""
    pytest.importorskip("jax")
    spec = ChurnSpec(trace_nodes=60, horizon_h=24.0 * 30, tp_sizes=(16, 32),
                     architectures=ARCHES, seed=7)
    ref = monte_carlo_replay(spec, 2, engine="batched", backend="numpy")
    plant(monkeypatch)
    got = monte_carlo_replay(spec, 2, engine="streamed", backend="jax",
                             chunk_snapshots=37)
    assert any(not np.array_equal(tg.faulty_gpus, tr.faulty_gpus)
               for tg, tr in zip(got.timelines, ref.timelines))


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
