"""Plain reference of the fat-tree placements (paper §4.3, §6.4, App. D).

One snapshot at a time, in plain Python over sets and lists, following
the paper's pseudocode: Algorithm 2 (K-hop components, TP groups popped
per component), Algorithm 3 (``p`` sub-lines), Algorithm 4 (sub-line
isolation then ToR alignment, residual pass), Algorithm 5 (binary search
over the number of satisfied constraints), the §6.4 greedy baseline
(random group order from Python's ``random.Random(seed)``) and static
DGX-class islands.  :func:`pair_counts` counts the DP-ring node pairs
that cross a ToR or an aggregation domain.
"""

from __future__ import annotations

import math
import random
from typing import Dict, List, Optional, Sequence, Set, Tuple

Placement = List[List[int]]


def components(order: Sequence[int], faults: Set[int], k: int) -> List[list]:
    """Healthy components along ``order``: ``k`` consecutive faults split."""
    comps, cur, gap = [], [], 0
    for u in order:
        if u in faults:
            gap += 1
            if gap >= k and cur:
                comps.append(cur)
                cur = []
            continue
        cur.append(u)
        gap = 0
    if cur:
        comps.append(cur)
    return comps


def dcn_free(order: Sequence[int], faults: Set[int], m: int,
             k: int) -> Placement:
    """Algorithm 2: groups of ``m`` consecutive nodes of each component."""
    groups = []
    for comp in components(order, faults, k):
        groups.extend(comp[i:i + m] for i in range(0, len(comp) - m + 1, m))
    return groups


def sublines(num_nodes: int, p: int) -> List[List[int]]:
    """Algorithm 3: sub-line ``i`` is nodes ``i, i + p, i + 2p, ...``."""
    return [list(range(i, (num_nodes // p) * p, p)) for i in range(p)]


def constrained(num_nodes: int, p: int, n_constraints: int, faults: Set[int],
                m: int, agg_domain: int, k: int) -> Placement:
    """Algorithm 4 at ``n_constraints`` satisfied constraints."""
    subs = sublines(num_nodes, p)
    n_domain = num_nodes // agg_domain
    n_align = max(0, min(n_constraints - len(subs), n_domain))
    n_sub = min(len(subs), n_constraints)
    # ToR alignment: in the first n_align domains a fault poisons its ToR
    eff = set(faults)
    for u in faults:
        if u < n_align * agg_domain:
            tor = u // p
            eff.update(range(tor * p, min((tor + 1) * p, num_nodes)))
    keyed, used = [], set()
    for idx in range(n_sub):
        by_domain: Dict[int, List[int]] = {}
        for u in subs[idx]:
            by_domain.setdefault(u // agg_domain, []).append(u)
        for dom, chunk in by_domain.items():
            for pos, grp in enumerate(dcn_free(chunk, eff, m, k)):
                keyed.append(((dom, tuple(u // p for u in grp), pos, idx),
                              grp))
                used.update(grp)
    keyed.sort(key=lambda kv: kv[0])
    placement = [grp for _, grp in keyed]
    order = [u for sub in subs for u in sub]
    placement.extend(dcn_free(order, set(faults) | used, m, k))
    return placement


def orchestrated(num_nodes: int, g: int, p: int, faults: Set[int], tp: int,
                 job_gpus: int, agg_domain: int,
                 k: int) -> Tuple[Optional[Placement], int]:
    """Algorithm 5: ``(placement, constraints)`` at the most constraints
    that still hold the job, or ``(None, -1)``."""
    m = tp // g
    lo, hi = 0, num_nodes // agg_domain + p
    best, level = None, -1
    while lo <= hi:
        mid = (lo + hi) // 2
        scheme = constrained(num_nodes, p, mid, faults, m, agg_domain, k)
        if len(scheme) * m * g >= job_gpus:
            best, level, lo = scheme, mid, mid + 1
        else:
            hi = mid - 1
    if best is None:
        return None, -1
    return best[:math.ceil(job_gpus / (m * g))], level


def greedy(num_nodes: int, g: int, p: int, faults: Set[int], tp: int,
           job_gpus: int, k: int, seed: int) -> Optional[Placement]:
    """§6.4 baseline: K-hop groups along the wiring order, ranks shuffled."""
    m = tp // g
    order = [u for sub in sublines(num_nodes, p) for u in sub]
    groups = dcn_free(order, faults, m, k)
    need = math.ceil(job_gpus / (m * g))
    if len(groups) < need:
        return None
    random.Random(seed).shuffle(groups)
    return groups[:need]


def dgx_islands(num_nodes: int, g: int, faults: Set[int], tp: int,
                job_gpus: int) -> Optional[Placement]:
    """Static islands of ``tp / g`` nodes in id order; a fault withholds
    its island."""
    m = tp // g
    need = math.ceil(job_gpus / (m * g))
    blocks = [list(range(b * m, b * m + m)) for b in range(num_nodes // m)
              if not any(u in faults for u in range(b * m, b * m + m))]
    return blocks[:need] if len(blocks) >= need else None


def pair_counts(placement: Optional[Placement], p: int,
                agg_domain: int) -> Dict[str, int]:
    """DP-ring pairs: rank ``r`` of group ``i`` talks to rank ``r`` of group
    ``i + 1``, and the ring closes when there is more than one group."""
    if not placement:
        return {"groups": 0, "dp_pairs": 0, "crossing_pairs": 0,
                "crossing_pod_pairs": 0}
    count, m = len(placement), len(placement[0])
    crossing = crossing_pod = pairs = 0
    if count > 1:
        pairs = count * m
        for i in range(count):
            a, b = placement[i], placement[(i + 1) % count]
            crossing += sum(x // p != y // p for x, y in zip(a, b))
            crossing_pod += sum(x // agg_domain != y // agg_domain
                                for x, y in zip(a, b))
    return {"groups": count, "dp_pairs": pairs, "crossing_pairs": crossing,
            "crossing_pod_pairs": crossing_pod}


def evaluate(faults: Set[int], cfg: dict, variant: str, tp: int,
             job_gpus: int) -> Dict[str, int]:
    """Pair counts, feasibility and (orchestrated) constraint level of one
    snapshot under one variant."""
    n, g, p = cfg["num_nodes"], cfg["gpus_per_node"], cfg["nodes_per_tor"]
    agg, k = cfg["agg_domain"], cfg["k"]
    level = -1
    if variant == "orchestrated":
        placement, level = orchestrated(n, g, p, faults, tp, job_gpus, agg, k)
    elif variant == "greedy":
        placement = greedy(n, g, p, faults, tp, job_gpus, k,
                           cfg["greedy_seed"])
    elif variant == "dgx-island":
        placement = dgx_islands(n, g, faults, tp, job_gpus)
    else:
        raise ValueError(f"unknown placement variant {variant!r}")
    out = pair_counts(placement, p, agg)
    out["feasible"] = int(placement is not None)
    out["n_constraints"] = level
    return out
