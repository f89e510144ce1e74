"""Appendix-A trace generator statistics (previously untested).

The paper's production trace: stationary mean faulty-node ratio 2.33% with
a heavy P99 tail (7.22%) from correlated burst incidents, and the Bayes
8->4 GPU-node conversion where each half-node fails with ~50.21%
probability given the parent fault.
"""

import numpy as np
import pytest

from repro.core.trace import (BAYES_SPLIT_P, FAULT_RATIO_4GPU,
                              MEAN_FAULT_RATIO_8GPU, FaultEvent, FaultTrace,
                              generate_trace, to_4gpu_trace)


def test_bayes_split_constant():
    """Appendix A: P(half-node faulty | 8-GPU node faulty) ~ 50.21%."""
    assert abs(BAYES_SPLIT_P - 0.5021) < 2e-3
    assert abs(FAULT_RATIO_4GPU - 0.0117) < 2e-4


@pytest.mark.parametrize("seed", range(3))
def test_stationary_mean_matches_paper(seed):
    tr = generate_trace(400, seed=seed)
    mean = tr.mean_fault_ratio(1000)
    assert abs(mean - MEAN_FAULT_RATIO_8GPU) < 1.5e-3
    # repair process calibration (exponential with mean 8h)
    assert abs(tr.mean_repair_h() - 8.0) < 0.5


@pytest.mark.parametrize("seed", range(3))
def test_heavy_p99_tail_from_bursts(seed):
    """Burst incidents must push P99 far above the stationary mean (the
    paper's 7.22% vs 2.33%), which i.i.d. per-node failures cannot do."""
    tr = generate_trace(400, seed=seed)
    series = tr.fault_ratio_series(1000)
    mean, p99 = float(series.mean()), float(np.percentile(series, 99))
    assert p99 > 2.5 * mean
    assert 0.05 < p99 < 0.15


def test_bayes_split_empirical():
    tr8 = generate_trace(200, horizon_h=60 * 24.0, seed=0)
    tr4 = to_4gpu_trace(tr8, seed=0)
    assert tr4.num_nodes == 2 * tr8.num_nodes
    # every parent event yields 1 or 2 half-node events at identical times
    child_times = {(e.start_h, e.end_h) for e in tr4.events}
    parent_times = {(e.start_h, e.end_h) for e in tr8.events}
    assert child_times == parent_times
    # per-half marginal: children / (2 * parents) estimates BAYES_SPLIT_P
    p_hat = len(tr4.events) / (2 * len(tr8.events))
    assert abs(p_hat - BAYES_SPLIT_P) < 0.03
    # conversion preserves the 4-GPU stationary mean
    mean4 = to_4gpu_trace(generate_trace(400, seed=1), seed=1)
    assert abs(mean4.mean_fault_ratio(1000) - FAULT_RATIO_4GPU) < 1.5e-3


def test_interval_edges_are_exact_boundaries():
    """The fault set must be constant on every [edge, next_edge) interval
    and the edge-sampled masks must equal the scalar faulty_at sets."""
    tr = to_4gpu_trace(generate_trace(30, horizon_h=15 * 24.0, seed=2), seed=2)
    edges = tr.interval_edges()
    assert edges[0] == 0.0
    assert np.all(np.diff(edges) > 0) and edges[-1] < tr.horizon_h
    assert np.isclose(tr.interval_durations(edges).sum(), tr.horizon_h)
    masks = tr.fault_masks(edges)
    rights = np.append(edges[1:], tr.horizon_h)
    for i, (lo, hi) in enumerate(zip(edges, rights)):
        at_edge = tr.faulty_at(lo)
        assert set(np.nonzero(masks[i])[0].tolist()) == at_edge
        assert tr.faulty_at((lo + hi) / 2) == at_edge   # constant inside


def test_event_deltas_reconstruct_faulty_at():
    tr = to_4gpu_trace(generate_trace(25, horizon_h=20 * 24.0, seed=5), seed=5)
    counts = np.zeros(tr.num_nodes, dtype=np.int32)
    deltas = tr.event_deltas()
    di = 0
    for t in tr.interval_edges():
        while di < len(deltas) and deltas[di][0] <= t:
            _, node, d = deltas[di]
            counts[node] += d
            di += 1
        assert set(np.nonzero(counts > 0)[0].tolist()) == tr.faulty_at(t)
    assert np.all(counts >= 0)


def _blocks(tr, ts, block):
    parts = list(tr.fault_mask_blocks(ts, block))
    assert all(p.shape == (min(block, len(ts) - i * block), tr.num_nodes)
               for i, p in enumerate(parts))
    return (np.concatenate(parts) if parts
            else np.zeros((0, tr.num_nodes), bool))


def _on_edges_trace():
    """Overlapping events on one node (a background fault with a burst
    inside it, and one starting where another ends) and events that start
    or end exactly on the block edges of the tests below (t = 7, 14)."""
    ev = [FaultEvent(0, 1.0, 9.0), FaultEvent(0, 3.0, 5.0),
          FaultEvent(0, 9.0, 14.0), FaultEvent(1, 7.0, 14.0),
          FaultEvent(2, 0.0, 7.0), FaultEvent(2, 6.5, 30.0),
          FaultEvent(3, 14.0, 15.0), FaultEvent(1, 20.0, 21.0)]
    return FaultTrace(5, 30.0, ev)


@pytest.mark.parametrize("block", [1, 7, 1024, 100_000])
@pytest.mark.parametrize("which", ["appendix_a", "on_edges", "empty"])
def test_fault_mask_blocks_equal_whole_masks(block, which):
    """Blocks of any size concatenate to ``fault_masks`` bit for bit, and
    every row is the scalar ``faulty_at`` set."""
    if which == "appendix_a":
        tr = to_4gpu_trace(generate_trace(40, horizon_h=20 * 24.0, seed=3),
                           seed=3)
        ts = tr.interval_edges()
    elif which == "on_edges":
        tr = _on_edges_trace()
        ts = np.arange(0.0, 28.0)          # integer hours: edges 7, 14, ...
    else:
        tr = FaultTrace(6, 10.0, [])
        ts = np.arange(0.0, 10.0, 0.5)
    whole = tr.fault_masks(ts)
    assert np.array_equal(_blocks(tr, ts, block), whole)
    for i, t in enumerate(ts):
        assert set(np.nonzero(whole[i])[0].tolist()) == tr.faulty_at(t)


def test_fault_mask_blocks_no_sample_times_and_bad_input():
    tr = _on_edges_trace()
    assert list(tr.fault_mask_blocks([], 4)) == []
    assert tr.fault_masks([]).shape == (0, 5)
    with pytest.raises(ValueError, match="ascending"):
        list(tr.fault_mask_blocks([2.0, 1.0], 4))
    with pytest.raises(ValueError, match="positive"):
        list(tr.fault_mask_blocks([1.0], 0))
