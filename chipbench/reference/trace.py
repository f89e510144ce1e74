"""Plain reference of the Appendix-A fault process (arXiv 2502.03885).

A realization is regenerated from its seed with the same NumPy
``default_rng`` call sequence as the program's generator: per 8-GPU node a
Poisson failure process with exponential repair, then cluster-wide burst
incidents, then the Bayes split of every 8-GPU event to its two 4-GPU
halves.  Events are ``(node, start_h, end_h)`` arrays; a node is faulty at
``t`` iff one of its events has ``start_h <= t < end_h``.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

Events = Tuple[np.ndarray, np.ndarray, np.ndarray]      # node, start, end

#: Appendix A's production trace of 8-GPU nodes: mean and P99 of the
#: faulty-node ratio.
MEAN_RATIO_8GPU = 0.0233
P99_RATIO_8GPU = 0.0722
#: Share of the steady-state downtime owed to cluster-wide bursts: the
#: generator's own assumption, which the paper does not give.
BURST_SHARE = 0.25


def split_p(mean_ratio: float) -> float:
    """Probability that a given half of a faulty 8-GPU node is faulty: the
    4-GPU fault ratio implied by i.i.d. GPU faults over the 8-GPU one."""
    per_gpu = 1.0 - (1.0 - mean_ratio) ** (1.0 / 8.0)
    return (1.0 - (1.0 - per_gpu) ** 4) / mean_ratio


def generate(nodes: int, horizon_h: float, mean_repair_h: float,
             seed: int) -> list:
    """``[(node, start_h, end_h)]`` of 8-GPU nodes: background failures
    node by node, then bursts until their node-hour budget is spent."""
    rng = np.random.default_rng(seed)
    events = []
    mean_ratio, p99_ratio = MEAN_RATIO_8GPU, P99_RATIO_8GPU
    bg = mean_ratio * (1.0 - BURST_SHARE)
    lam = bg / ((1.0 - bg) * mean_repair_h)
    for node in range(nodes):
        t = float(rng.exponential(1.0 / lam))
        while t < horizon_h:
            dur = float(rng.exponential(mean_repair_h))
            events.append((node, t, min(t + dur, horizon_h)))
            t += dur + float(rng.exponential(1.0 / lam))
    budget = mean_ratio * BURST_SHARE * horizon_h * nodes
    spent = 0.0
    while spent < budget:
        frac = float(rng.uniform(0.5, 1.0)) * max(p99_ratio - bg, 0.01)
        count = max(1, int(frac * nodes))
        start = float(rng.uniform(0.0, horizon_h))
        dur = float(rng.exponential(mean_repair_h))
        for node in rng.choice(nodes, size=count, replace=False):
            events.append((int(node), start, min(start + dur, horizon_h)))
        spent += count * dur
    return events


def split(events: list, half_p: float, seed: int) -> Events:
    """Each 8-GPU event fails its two halves both with probability
    ``2 half_p - 1``, else one half chosen by a fair coin."""
    rng = np.random.default_rng(seed)
    both = max(0.0, 2.0 * half_p - 1.0)
    out = []
    for node, start, end in events:
        if rng.random() < both:
            halves = (2 * node, 2 * node + 1)
        else:
            halves = (2 * node,) if bool(rng.integers(0, 2)) \
                else (2 * node + 1,)
        out.extend((h, start, end) for h in halves)
    if not out:
        return (np.zeros(0, np.int64), np.zeros(0), np.zeros(0))
    node, start, end = zip(*out)
    return (np.array(node, np.int64), np.array(start, np.float64),
            np.array(end, np.float64))


def realization(trace_nodes: int, horizon_h: float, mean_repair_h: float,
                seed: int) -> Events:
    """4-GPU-node events of one realization of the process."""
    return split(generate(trace_nodes, horizon_h, mean_repair_h, seed),
                 split_p(MEAN_RATIO_8GPU), seed)


def interval_edges(events: Events, horizon_h: float, ft=np.float64):
    """Sorted left edges of the intervals on which the fault set is
    constant: 0 and every event start or end strictly inside the horizon,
    each once."""
    _, start, end = events
    times = np.concatenate([start, end])
    inside = times[(times > 0.0) & (times < horizon_h)]
    return np.unique(np.concatenate([[0.0], inside])).astype(ft)


def faulty_masks(events: Events, ts, num_nodes: int) -> np.ndarray:
    """``(len(ts), num_nodes)`` masks: row ``i`` marks the nodes with an
    event active at ``ts[i]``."""
    node, start, end = events
    masks = np.zeros((len(ts), num_nodes), dtype=bool)
    for i, t in enumerate(ts):
        masks[i, node[(start <= t) & (t < end)]] = True
    return masks
