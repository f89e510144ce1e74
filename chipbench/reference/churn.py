"""Plain reference of the churn tables: time-weighted integration of the
per-interval grids of one realization, and the summary over realizations.

Interval ``b`` of a realization lasts from its edge to the next one (the
last to the horizon); a realization's table weights every interval's
waste ratio and GPU counts by that duration.  Every integral contracts
the interval axis of the float grid with one ``np.einsum("abt,b->at")``,
the summation order the program documents, so the values are compared
exactly.  ``ft`` is the float type of every step: float64 for the
reference, float32 for the benchmark's lower-precision control.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np


def _integrals(edges, horizon_h: float, total, faulty, placed, ft):
    """``(time-mean waste, wasted GPU-h, goodput GPU-h, placed share)``,
    each ``(A, T)``."""
    durations = np.diff(np.append(np.asarray(edges, ft), ft(horizon_h)))
    tot = np.broadcast_to(total[:, None, :], placed.shape)
    wasted = tot - faulty - placed
    ratio = np.divide(wasted.astype(ft), tot.astype(ft),
                      out=np.zeros(placed.shape, ft), where=tot != 0)
    mean = np.einsum("abt,b->at", ratio, durations / ft(horizon_h))
    wasted_h = np.einsum("abt,b->at", wasted.astype(ft), durations)
    good_h = np.einsum("abt,b->at", placed.astype(ft), durations)
    denom = total.astype(ft) * ft(horizon_h)
    share = np.divide(good_h, denom, out=np.zeros(denom.shape, ft),
                      where=denom != 0)
    return mean, wasted_h, good_h, share


def integrated_waste_table(names: Sequence[str], tps: Sequence[int], edges,
                           horizon_h: float, total, faulty, placed,
                           ft=np.float64) -> List[Dict]:
    """Per (architecture, TP) of one realization: time-mean waste ratio,
    wasted and placed GPU-hours, and placed GPU-hours over all GPU-hours."""
    mean, wasted_h, good_h, share = _integrals(edges, horizon_h, total,
                                               faulty, placed, ft)
    return [{"architecture": name, "tp_size": int(tp),
             "time_mean_waste": float(mean[ai, ti]),
             "wasted_gpu_h": float(wasted_h[ai, ti]),
             "goodput_gpu_h": float(good_h[ai, ti]),
             "placed_share": float(share[ai, ti])}
            for ai, name in enumerate(names) for ti, tp in enumerate(tps)]


def summary_table(names: Sequence[str], tps: Sequence[int], realizations,
                  ft=np.float64) -> List[Dict]:
    """Per (architecture, TP) over realizations ``(edges, horizon_h, total,
    faulty, placed)``: mean and P99 (linear) of the time-mean waste ratio,
    and the mean placed share."""
    if not realizations:
        return []
    parts = [_integrals(*r, ft=ft) for r in realizations]
    waste = np.stack([p[0] for p in parts])
    share = np.stack([p[3] for p in parts])
    return [{"architecture": name, "tp_size": int(tp),
             "traces": len(realizations),
             "mean_waste": float(waste[:, ai, ti].mean()),
             "p99_waste": float(np.percentile(waste[:, ai, ti], 99)),
             "mean_placed_share": float(share[:, ai, ti].mean())}
            for ai, name in enumerate(names) for ti, tp in enumerate(tps)]
