"""Batched scenario engine + incremental orchestrator equivalence tests.

Property-style but hypothesis-free (seeded NumPy RNG) so they run in the
fast CI lane on a bare install:

  * batched ``evaluate_batch`` == scalar ``evaluate`` bit-for-bit, for every
    architecture, across random fault masks and awkward TP sizes;
  * batched fault_sim wrappers == scalar trace metrics bit-for-bit;
  * incremental orchestration == full re-orchestration after random
    fault/repair sequences;
  * sweep runner grid == scalar reference grid, chunking included.
"""

import numpy as np
import pytest

from repro.core.control_plane import ClusterManager
from repro.core.fault_sim import (fault_waiting_time,
                                  fault_waiting_time_batched, max_job_scale,
                                  max_job_scale_batched, waste_over_trace,
                                  waste_over_trace_batched,
                                  waste_vs_fault_ratio,
                                  waste_vs_fault_ratio_batched)
from repro.core.arch import make_model, names as arch_names
from repro.core.hbd_models import InfiniteHBDModel, default_suite
from repro.core.orchestrator import (IncrementalOrchestrator,
                                     deployment_strategy,
                                     orchestrate_dcn_free)
from repro.core.trace import generate_trace, iid_fault_masks, iid_fault_sets, to_4gpu_trace

AWKWARD_TPS = [4, 8, 16, 24, 32, 48, 64, 128]


# ------------------------------------------------- batched == scalar models

@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("num_nodes", [97, 720])
def test_evaluate_batch_matches_scalar(seed, num_nodes):
    rng = np.random.default_rng(seed)
    ratio = rng.uniform(0.0, 0.3)
    masks = rng.random((12, num_nodes)) < ratio
    # every registered architecture (rival zoo included), not a hand-kept
    # list -- a new registration is covered here with zero edits -- plus
    # the InfiniteHBD configuration corners outside the registry
    suite = [make_model(a, num_nodes) for a in arch_names()] + [
        InfiniteHBDModel(num_nodes, 4, k=3, closed_ring=False),
        InfiniteHBDModel(num_nodes, 4, k=1),
    ]
    for model in suite:
        grid = model.evaluate_batch(masks, AWKWARD_TPS)
        for si in range(masks.shape[0]):
            faults = set(np.nonzero(masks[si])[0].tolist())
            for ti, tp in enumerate(AWKWARD_TPS):
                ref = model.evaluate(faults, tp)
                got = grid.result(si, ti)
                assert (got.total_gpus, got.faulty_gpus, got.placed_gpus) == \
                    (ref.total_gpus, ref.faulty_gpus, ref.placed_gpus), \
                    (model.name, si, tp)


def test_evaluate_batch_extreme_masks():
    """All-healthy and all-faulty snapshots, including wrap-merge paths."""
    n = 64
    masks = np.stack([np.zeros(n, bool), np.ones(n, bool),
                      np.arange(n) < 62,           # only a tail sliver healthy
                      ~(np.arange(n) < 2)])        # only a head sliver healthy
    for model in [make_model(a, n) for a in arch_names()]:
        grid = model.evaluate_batch(masks, [16, 32])
        for si in range(masks.shape[0]):
            faults = set(np.nonzero(masks[si])[0].tolist())
            for ti, tp in enumerate([16, 32]):
                ref = model.evaluate(faults, tp)
                got = grid.result(si, ti)
                assert got.placed_gpus == ref.placed_gpus
                assert got.faulty_gpus == ref.faulty_gpus


def test_fault_masks_match_faulty_at():
    tr = to_4gpu_trace(generate_trace(100, seed=3))
    ts = tr.sample_times(64)
    masks = tr.fault_masks(ts)
    for i, t in enumerate(ts):
        assert set(np.nonzero(masks[i])[0].tolist()) == tr.faulty_at(t)


def test_iid_masks_match_iid_sets():
    masks = iid_fault_masks(300, 0.07, 15, seed=5)
    for row, ref in zip(masks, iid_fault_sets(300, 0.07, 15, seed=5)):
        assert set(np.nonzero(row)[0].tolist()) == ref


# --------------------------------------------- batched == scalar fault_sim

def test_batched_trace_metrics_bit_for_bit():
    tr4 = to_4gpu_trace(generate_trace(120, seed=1))
    for model in default_suite(100, 4):
        for tp in (16, 32):
            ref = waste_over_trace(model, tr4, tp, 60)
            [got] = waste_over_trace_batched(model, tr4, [tp], 60)
            assert got.mean_waste == ref.mean_waste
            assert got.p50_waste == ref.p50_waste
            assert got.p99_waste == ref.p99_waste
            assert np.array_equal(got.series, ref.series)
            assert max_job_scale(model, tr4, tp, 40) == \
                max_job_scale_batched(model, tr4, [tp], 40)[0]
            job = 300 // tp * tp
            assert fault_waiting_time(model, tr4, tp, job, 60) == \
                fault_waiting_time_batched(model, tr4, tp, [job], 60)[0]
        assert waste_vs_fault_ratio(model, 32, [0.02, 0.08], 8) == \
            waste_vs_fault_ratio_batched(model, 32, [0.02, 0.08], 8)


# ------------------------------------------------------------ sweep runner

def test_run_sweep_matches_scalar_reference():
    from repro.sim import IIDSnapshots, ScenarioSpec, run_sweep, run_sweep_scalar
    spec = ScenarioSpec(num_nodes=144,
                        snapshots=IIDSnapshots(0.06, samples=25, seed=2),
                        tp_sizes=(8, 32, 48))
    batched = run_sweep(spec, chunk_snapshots=7)   # force chunk boundaries
    scalar = run_sweep_scalar(spec)
    assert batched.names == scalar.names
    assert np.array_equal(batched.placed_gpus, scalar.placed_gpus)
    assert np.array_equal(batched.faulty_gpus, scalar.faulty_gpus)
    assert np.array_equal(batched.total_gpus, scalar.total_gpus)


def test_trace_snapshots_default_covers_cluster():
    """Default TraceSnapshots must span the swept cluster -- a narrower
    trace would silently read the tail nodes as permanently healthy."""
    from repro.sim import ScenarioSpec, TraceSnapshots
    snaps = TraceSnapshots(samples=5, seed=0)
    assert snaps.masks(1002).shape[1] >= 1002          # 4-GPU conversion
    assert TraceSnapshots(samples=5, seed=0,
                          convert_4gpu=False).masks(333).shape[1] >= 333
    spec = ScenarioSpec(num_nodes=1002, snapshots=snaps, tp_sizes=(32,),
                        architectures=("big-switch",))
    from repro.sim import run_sweep
    assert run_sweep(spec).placed_gpus.shape == (1, 5, 1)


def test_sweep_tables_shapes():
    from repro.sim import (IIDSnapshots, ScenarioSpec, fault_waiting_table,
                           max_job_table, run_sweep, to_csv, waste_table)
    spec = ScenarioSpec(num_nodes=72,
                        snapshots=IIDSnapshots(0.05, samples=10, seed=0),
                        tp_sizes=(16, 32), architectures=("big-switch",
                                                          "infinitehbd-k3"))
    res = run_sweep(spec)
    assert len(waste_table(res)) == 4
    assert len(max_job_table(res)) == 4
    assert len(fault_waiting_table(res, [128, 256])) == 8
    csv = to_csv(waste_table(res))
    assert csv.splitlines()[0] == \
        "architecture,tp_size,mean_waste,p50_waste,p99_waste"
    assert len(csv.splitlines()) == 5


# ------------------------------- vectorised table reductions == per series

def _per_series_tables(names, tps, total, faulty, placed, percentile):
    """The per-(architecture, TP) loop the one-call reductions replaced:
    1-D float64 slices, one mean and one percentile call per statistic."""
    tot = np.broadcast_to(total[:, None, :], placed.shape)
    waste = np.divide(tot - faulty - placed, tot,
                      out=np.zeros(placed.shape), where=tot != 0)
    waste_rows, job_rows = [], []
    for ai, name in enumerate(names):
        for ti, tp in enumerate(tps):
            series = waste[ai, :, ti]
            waste_rows.append({
                "architecture": name, "tp_size": int(tp),
                "mean_waste": float(series.mean()),
                "p50_waste": float(np.percentile(series, 50)),
                "p99_waste": float(np.percentile(series, 99))})
            gpus = float(np.percentile(placed[ai, :, ti].astype(float),
                                       percentile))
            t = int(total[ai, ti])
            job_rows.append({
                "architecture": name, "tp_size": int(tp),
                "max_job_gpus": gpus,
                "fraction": gpus / t if t else 0.0})
    return waste, waste_rows, job_rows


@pytest.mark.parametrize("snapshots", [1, 2, 7, 129, 256, 1000, 8192])
def test_vectorised_table_reductions_match_per_series_loop(snapshots):
    from repro.core.reductions import percentile_capacity, waste_stats
    from repro.sim import max_job_table, waste_table
    from repro.sim.engine import SweepResult
    rng = np.random.default_rng(snapshots)
    names = ["a0", "no-gpus", "ties", "constant", "a4"]
    tps = np.array([16, 32, 64])
    A, S, T = len(names), snapshots, len(tps)
    total = rng.integers(1024, 131072, (A, T))
    total[1] = 0                                 # an architecture with no GPUs
    faulty = np.minimum(rng.integers(0, 4096, (A, S, T)), total[:, None, :])
    placed = (rng.random((A, S, T))
              * (total[:, None, :] - faulty)).astype(np.int64)
    placed[2] = np.minimum(rng.choice([0, 512, 1024], (S, T)),
                           total[2] - faulty[2])  # heavily tied values
    faulty[3], placed[3] = faulty[3, :1], placed[3, :1]  # all-equal series
    result = SweepResult(spec=None, names=names, tp_sizes=tps,
                         total_gpus=total, faulty_gpus=faulty,
                         placed_gpus=placed)
    for percentile in (5.0, 50.0):
        waste, waste_rows, job_rows = _per_series_tables(
            names, tps, total, faulty, placed, percentile)
        assert np.array_equal(result.waste_ratio, waste)
        assert waste_table(result) == waste_rows
        assert max_job_table(result, percentile) == job_rows
    # a 1-D series is the S-only case: Python floats, the same values
    for ai in range(A):
        for ti in range(T):
            stats = waste_stats(waste[ai, :, ti])
            row = waste_rows[ai * T + ti]
            assert all(type(v) is float for v in stats)
            assert stats == (row["mean_waste"], row["p50_waste"],
                             row["p99_waste"])
            gpus = percentile_capacity(placed[ai, :, ti], 50.0)
            assert type(gpus) is float
            assert gpus == job_rows[ai * T + ti]["max_job_gpus"]


# ------------------------------------------- incremental == full orchestration

@pytest.mark.parametrize("seed", range(6))
def test_incremental_equals_full_reorchestration(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.choice([16, 64, 200]))
    k = int(rng.choice([1, 2, 3]))
    m = int(rng.choice([1, 2, 4, 8]))
    order = list(deployment_strategy(n, 8).order) if seed % 2 else list(range(n))
    init = set(rng.choice(n, size=n // 6, replace=False).tolist()) \
        if seed % 3 == 0 else set()
    inc = IncrementalOrchestrator(order, m, k, set(init))
    faults = set(init)
    for _ in range(70):
        if faults and rng.random() < 0.45:
            u = int(sorted(faults)[rng.integers(len(faults))])
            faults.discard(u)
            inc.repair(u)
        else:
            u = int(rng.integers(n))
            faults.add(u)
            inc.fault(u)
        ref = orchestrate_dcn_free(order, faults, m, k)
        assert inc.placement() == ref
        assert inc.capacity_groups() == len(ref)


def test_incremental_untracked_and_idempotent_events():
    inc = IncrementalOrchestrator(list(range(16)), 2, 2)
    base = inc.capacity_groups()
    inc.fault(99)                       # untracked node: bookkeeping only
    assert inc.capacity_groups() == base
    inc.fault(3)
    cap = inc.capacity_groups()
    inc.fault(3)                        # double fault: no-op
    assert inc.capacity_groups() == cap
    inc.repair(3)
    inc.repair(3)                       # double repair: no-op
    assert inc.capacity_groups() == base
    assert inc.placement() == orchestrate_dcn_free(list(range(16)), {99}, 2, 2)


# --------------------------------------------------- control-plane fast path

def test_cluster_manager_incremental_matches_full():
    """The delta-updated capacity tracker must not change replan decisions."""
    events = [("fault", {3, 4}), ("fault", {10}), ("repair", {4}),
              ("fault", {17, 18, 19}), ("repair", {3}), ("repair", {10})]
    plans = {}
    for incremental in (False, True):
        cm = ClusterManager(64, 4, k=3, nodes_per_tor=8, agg_domain=32,
                            incremental=incremental)
        out = []
        t = 0.0
        for kind, nodes in events:
            fn = cm.on_fault if kind == "fault" else cm.on_repair
            ev = fn(t, nodes, tp_size=16, dp_size=8)
            out.append(ev.plan.placement)
            t += 60.0
        plans[incremental] = out
    assert plans[True] == plans[False]


def test_placeable_gpus_tracks_faults():
    cm = ClusterManager(64, 4, k=3, nodes_per_tor=8, agg_domain=32)
    full = cm.placeable_gpus(16)
    assert full == 64 * 4
    cm.on_fault(0.0, {5}, tp_size=16, dp_size=4)
    assert cm.placeable_gpus(16) <= full - 4
    cm.on_repair(10.0, {5}, tp_size=16, dp_size=4)
    assert cm.placeable_gpus(16) == full
