"""Plain reference of the table reductions (paper Figs. 13-15, 17c).

Each function rebuilds a table of the program from integer grids, in the
float type ``ft`` (float64 for the reference, a narrower type for the
benchmark's lower-precision control), with the row order and keys of the
program's table.  :func:`count_off` compares two tables value by value.
"""

from __future__ import annotations

import math
from typing import Dict, List, Sequence

import numpy as np


def waste_table(names: Sequence[str], tps: Sequence[int], total, faulty,
                placed, ft=np.float64) -> List[Dict]:
    """Per (architecture, TP): mean, P50 and P99 over snapshots of
    ``(healthy - placed) / total`` (0 where an architecture has no GPUs)."""
    rows = []
    for ai, name in enumerate(names):
        for ti, tp in enumerate(tps):
            tot = int(total[ai, ti])
            wasted = (tot - faulty[ai, :, ti] - placed[ai, :, ti]).astype(ft)
            waste = (wasted / ft(tot) if tot
                     else np.zeros(wasted.shape, ft))
            rows.append({"architecture": name, "tp_size": int(tp),
                         "mean_waste": float(waste.mean()),
                         "p50_waste": float(np.percentile(waste, 50)),
                         "p99_waste": float(np.percentile(waste, 99))})
    return rows


def max_job_table(names: Sequence[str], tps: Sequence[int], total, placed,
                  percentile: float = 5.0, ft=np.float64) -> List[Dict]:
    """Per (architecture, TP): the ``percentile`` of placeable GPUs over
    snapshots, and its share of the architecture's GPUs."""
    rows = []
    for ai, name in enumerate(names):
        for ti, tp in enumerate(tps):
            gpus = float(np.percentile(placed[ai, :, ti].astype(ft),
                                       percentile))
            tot = int(total[ai, ti])
            rows.append({"architecture": name, "tp_size": int(tp),
                         "max_job_gpus": gpus,
                         "fraction": float(ft(gpus) / ft(tot)) if tot
                         else 0.0})
    return rows


def dp_tp_bytes(model: dict, tp: int) -> tuple:
    """Per-step bytes of one DP-ring link and of one TP-group member for a
    dense transformer (Megatron volumes): TP does 4 ring all-reduces of the
    activations per layer, DP one ring all-reduce of the bf16 gradient
    shard."""
    h, f, dp = model["hidden"], model["ffn"], model["dp_size"]
    layers, b = model["layers"], model["bytes_per_elem"]
    # attention and MLP weights per layer, untied input and output embedding
    params = float((4 * h * h + model["ffn_mats"] * h * f) * layers
                   + model["vocab"] * h * 2)
    x_bytes = model["micro_batch"] * model["seq"] * h * b
    tp_bytes = 4 * 2 * x_bytes * (tp - 1) / tp * layers if tp > 1 else 0.0
    dp_bytes = 2 * (b * params / tp) * (dp - 1) / dp if dp > 1 else 0.0
    return dp_bytes, tp_bytes


def traffic_table(variants: Sequence[str], ratios: Sequence[float],
                  tps: Sequence[int], group_nodes: Sequence[int], grids: dict,
                  model: dict, ft=np.float64) -> List[Dict]:
    """Per (TP, variant, fault ratio): share of feasible snapshots, and the
    mean over them of the cross-ToR, cross-domain and DP-crossing shares of
    DCN volume; the orchestrated variant adds its mean constraint level.

    ``grids`` holds ``groups``, ``dp_pairs``, ``crossing_pairs``,
    ``crossing_pod_pairs``, ``feasible`` as ``(V, R, S, T)`` and
    ``n_constraints`` as ``(R, S, T)``.
    """
    def share(num, den):
        num, den = np.asarray(num, ft), np.asarray(den, ft)
        out = np.zeros(num.shape, ft)
        np.divide(num, den, out=out, where=den != 0)
        return out

    rows = []
    for ti, tp in enumerate(tps):
        dp_b, tp_b = (ft(x) for x in dp_tp_bytes(model, int(tp)))
        g = {k: v[..., ti] for k, v in grids.items()}
        dp_vol = g["dp_pairs"].astype(ft) * dp_b
        total = dp_vol + (g["groups"] * int(group_nodes[ti])).astype(ft) * tp_b
        shares = {
            "cross_tor_share": share(g["crossing_pairs"].astype(ft) * dp_b,
                                     total),
            "cross_pod_share": share(g["crossing_pod_pairs"].astype(ft) * dp_b,
                                     total),
            "dp_cross_share": share(g["crossing_pairs"], g["dp_pairs"]),
        }
        for vi, variant in enumerate(variants):
            for ri, ratio in enumerate(ratios):
                feas = g["feasible"][vi, ri].astype(bool)
                row = {"variant": variant, "fault_ratio": float(ratio),
                       "tp_size": int(tp),
                       "feasible_share": float(feas.astype(ft).mean())}
                for key, grid in shares.items():
                    cell = grid[vi, ri][feas]
                    row[f"mean_{key}"] = float(cell.mean()) if cell.size \
                        else None
                if variant == "orchestrated":
                    nc = g["n_constraints"][ri]
                    nc = nc[nc >= 0].astype(ft)
                    row["mean_constraints"] = float(nc.mean()) if nc.size \
                        else None
                rows.append(row)
    return rows


def cross_tor_curve(table: List[Dict], tp: int,
                    variant: str = "orchestrated") -> Dict[float, float]:
    """``{fault ratio: mean cross-ToR share}`` of one variant at one TP."""
    return {r["fault_ratio"]: r["mean_cross_tor_share"] for r in table
            if r["variant"] == variant and r["tp_size"] == tp}


def _same(a, b) -> bool:
    if isinstance(a, float) and isinstance(b, float):
        return a == b or (math.isnan(a) and math.isnan(b))
    return a == b


def count_off(got, want) -> int:
    """Values of ``got`` that differ from ``want``: tables are lists of row
    dicts or dicts of values; a missing or extra value counts as one."""
    if isinstance(want, dict):
        got = got if isinstance(got, dict) else {}
        return sum(not (k in got and _same(got[k], v))
                   for k, v in want.items()) + len(set(got) - set(want))
    got = list(got)
    off = abs(len(got) - len(want)) * max((len(r) for r in want), default=1)
    return off + sum(count_off(g, w) for g, w in zip(got, want))
