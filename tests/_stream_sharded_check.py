"""Streaming-engine equivalence under forced multi-device sharding.

Run in a subprocess (XLA_FLAGS set before jax import) so the main pytest
process keeps one device.  Prints 'OK stream_sharded' on success.
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np  # noqa: E402
import jax  # noqa: E402

from repro.churn.monte_carlo import ChurnSpec, monte_carlo_replay  # noqa: E402
from repro.sim.jax_backend import device_masks  # noqa: E402
from repro.sim.engine import evaluate_mask_stream, evaluate_masks, run_sweep  # noqa: E402
from repro.sim.sources import IntervalDeltas, MaskGen  # noqa: E402
from repro.sim.scenario import CounterIIDSnapshots, ScenarioSpec  # noqa: E402

ARCHES = ("infinitehbd-k3", "nvl-72")


def main():
    assert len(jax.devices()) == 8, jax.devices()

    # sample/chunk counts off the 8-device grid so tail blocks pad
    spec = ScenarioSpec(num_nodes=77,
                        snapshots=CounterIIDSnapshots(0.09, 93, seed=4),
                        tp_sizes=(8, 32), architectures=ARCHES)
    models = spec.models()
    masks = spec.snapshots.masks(spec.num_nodes)
    ref = evaluate_masks(models, spec.tp_sizes, masks, backend="numpy")
    chunks = [masks[:11], masks[11:12], masks[12:60], masks[60:]]
    for chunk_snapshots in (5, 1024):
        got = evaluate_mask_stream(models, spec.tp_sizes, chunks, 93,
                                   chunk_snapshots=chunk_snapshots,
                                   backend="jax")
        assert got[3] == "jax"
        for g, r in zip(got[:3], ref[:3]):
            assert np.array_equal(g, r), chunk_snapshots

    # run_sweep's streamed counter-mask path, sharded
    sref = run_sweep(spec, masks=masks, backend="numpy")
    sgot = run_sweep(spec, chunk_snapshots=13, backend="jax")
    assert sgot.backend == "jax"
    assert np.array_equal(sgot.total_gpus, sref.total_gpus)
    assert np.array_equal(sgot.faulty_gpus, sref.faulty_gpus)
    assert np.array_equal(sgot.placed_gpus, sref.placed_gpus)

    # streamed Monte-Carlo churn, sharded jax (masks built on the
    # devices) vs batched numpy
    cspec = ChurnSpec(trace_nodes=40, horizon_h=24.0 * 20, tp_sizes=(16,),
                      architectures=ARCHES, seed=2)
    cref = monte_carlo_replay(cspec, 2, engine="batched", backend="numpy")
    cgot = monte_carlo_replay(cspec, 2, engine="streamed", backend="jax",
                              chunk_snapshots=7)
    for tg, tr in zip(cgot.timelines, cref.timelines):
        assert np.array_equal(tg.faulty_gpus, tr.faulty_gpus)
        assert np.array_equal(tg.placed_gpus, tr.placed_gpus)
        assert np.array_equal(tg.total_gpus, tr.total_gpus)

    # interval masks built on the device, each block's rows split over
    # the eight devices (7-row blocks pad to 8), deltas beyond one call
    trs = [cspec.trace(r) for r in range(2)]
    pieces = [(tr, tr.interval_edges()) for tr in trs]
    want = np.concatenate([tr.fault_masks(ts) for tr, ts in pieces])
    for block, capacity in ((7, 3), (64, 4096)):
        got = np.concatenate(list(device_masks(
            IntervalDeltas(pieces, trs[0].num_nodes, capacity), block)))
        assert np.array_equal(got, want), (block, capacity)

    # counter masks drawn on the devices, 13-row blocks padded to 16
    got = np.concatenate(list(device_masks(
        MaskGen(93, spec.num_nodes, 0.09, seed=4), 13)))
    assert np.array_equal(got, masks)

    print("OK stream_sharded")


if __name__ == "__main__":
    main()
