"""Plain reference of the HBD architectures' placement (paper §6.2, Table 1).

Each function takes a ``(rows, nodes)`` bool fault-mask batch and returns
``(total (T,), faulty (rows, T), placed (rows, T))`` GPU counts, written
from the architectures' definitions and not from the program's kernels.
``dt`` is the integer type every count is computed in: int64 for the
reference, a narrower type for the benchmark's lower-precision control.
Constants go through :func:`_cast`, so a narrow type wraps as it would on
a device instead of raising.
"""

from __future__ import annotations

from typing import Callable, Dict, Sequence, Tuple

import numpy as np

Grid = Tuple[np.ndarray, np.ndarray, np.ndarray]


def _cast(x, dt):
    return np.asarray(x).astype(dt)


def _grid(total, faulty, placed, dt) -> Grid:
    rows, t = placed.shape
    return (_cast(np.broadcast_to(total, (t,)), dt),
            _cast(np.broadcast_to(faulty, (rows, t)), dt),
            _cast(placed, dt))


def _count(masks: np.ndarray, lo: int, hi: int, dt) -> np.ndarray:
    """Faulty nodes among ``[lo, hi)`` of every row."""
    return _cast(masks[:, lo:hi].sum(axis=1), dt)


def big_switch(masks, tps, g, dt, **_) -> Grid:
    """One ideal switch: any healthy GPU joins any group."""
    n = masks.shape[1]
    total = _cast(n * g, dt)
    faulty = _count(masks, 0, n, dt) * _cast(g, dt)
    placed = np.stack([((total - faulty) // _cast(tp, dt)) * _cast(tp, dt)
                       for tp in tps], axis=1)
    return _grid(total, faulty[:, None], placed, dt)


def islands(masks, tps, g, dt, *, hbd_gpus: int, spare_gpus: int, **_) -> Grid:
    """Switched islands of ``hbd_gpus``: faults eat the spares first, then
    compute GPUs; inside an island any healthy compute GPU joins a group."""
    rows, n = masks.shape
    per = hbd_gpus // g
    count = n // per
    f_gpus = _cast(masks[:, :count * per].reshape(rows, count, per).sum(2),
                   dt) * _cast(g, dt)
    over = np.maximum(f_gpus - _cast(spare_gpus, dt), _cast(0, dt))
    avail = np.maximum(_cast(hbd_gpus - spare_gpus, dt) - over, _cast(0, dt))
    placed = np.stack([((avail // _cast(tp, dt)) * _cast(tp, dt)).sum(1,
                                                                     dtype=dt)
                       for tp in tps], axis=1)
    return _grid(_cast(count * hbd_gpus, dt), f_gpus.sum(1, dtype=dt)[:, None],
                 placed, dt)


def cubes(masks, tps, g, dt, *, cube_gpus: int, **_) -> Grid:
    """TPUv4: a TP group up to a cube is a fixed sub-block of its cube (a
    block that runs past the cube's end takes the next cube's nodes, and
    nodes past the cluster read healthy); larger groups are unions of
    fault-free cubes."""
    rows, n = masks.shape
    per = cube_gpus // g
    count = n // per
    padded = np.concatenate([masks, np.zeros((rows, per), bool)], axis=1)
    placed = []
    for tp in tps:
        if tp <= cube_gpus:
            bn = max(1, tp // g)
            starts = (np.arange(count)[:, None] * per
                      + np.arange(0, per, bn)[None, :]).ravel()
            blocks = padded[:, starts[:, None] + np.arange(bn)[None, :]]
            free = ~blocks.any(2)
            placed.append(_cast(free.sum(1), dt) * _cast(tp, dt))
        else:
            whole = ~masks[:, :count * per].reshape(rows, count, per).any(2)
            placed.append((_cast(whole.sum(1), dt) * _cast(cube_gpus, dt)
                           // _cast(tp, dt)) * _cast(tp, dt))
    faulty = _count(masks, 0, count * per, dt) * _cast(g, dt)
    return _grid(_cast(count * cube_gpus, dt), faulty[:, None],
                 np.stack(placed, 1), dt)


def static_rings(masks, tps, g, dt, **_) -> Grid:
    """SiP-Ring: fixed rings of exactly TP size; a fault kills its ring."""
    rows, n = masks.shape
    total, faulty, placed = [], [], []
    for tp in tps:
        per = max(1, tp // g)
        count = n // per
        rings = masks[:, :count * per].reshape(rows, count, per)
        placed.append(_cast((~rings.any(2)).sum(1), dt) * _cast(tp, dt))
        faulty.append(_count(masks, 0, count * per, dt) * _cast(g, dt))
        total.append(count * per * g)
    return _grid(_cast(total, dt), np.stack(faulty, 1), np.stack(placed, 1),
                 dt)


def _khop_components(row: np.ndarray, k: int, closed: bool) -> list:
    """Healthy-node counts of the K-hop line's components: a run of ``k`` or
    more consecutive faults splits the line; on a closed ring the first
    and last components join when the faults wrapping round the ends
    number fewer than ``k``."""
    n = row.size
    faults = np.flatnonzero(row)
    if faults.size == 0:
        return [n]
    new_run = np.concatenate([[True], np.diff(faults) != 1])
    run_start = faults[new_run]
    run_len = np.diff(np.append(np.flatnonzero(new_run), faults.size))
    run_end = run_start + run_len           # one past the run's last node
    cuts = [(s, e) for s, e, ln in zip(run_start, run_end, run_len) if ln >= k]
    bounds = [0] + [x for s, e in cuts for x in (s, e)] + [n]
    comps = []
    for lo, hi in zip(bounds[0::2], bounds[1::2]):
        healthy = (hi - lo) - int(((faults >= lo) & (faults < hi)).sum())
        if healthy:
            comps.append(healthy)
    if closed and len(comps) > 1:
        healthy_nodes = np.flatnonzero(~row)
        wrap_gap = healthy_nodes[0] + n - healthy_nodes[-1] - 1
        if wrap_gap < k:
            comps = [comps[0] + comps[-1]] + comps[1:-1]
    return comps


def khop_ring(masks, tps, g, dt, *, k: int, closed_ring: bool = True,
              **_) -> Grid:
    """InfiniteHBD: K-hop ring over the whole cluster; each component
    places whole TP groups of ``tp / g`` consecutive healthy nodes."""
    rows, n = masks.shape
    placed = np.zeros((rows, len(tps)), dtype=dt)
    for r in range(rows):
        comps = _cast(_khop_components(masks[r], k, closed_ring), dt)
        for ti, tp in enumerate(tps):
            m = _cast(max(1, tp // g), dt)
            placed[r, ti] = ((comps // m) * m).sum(dtype=dt) * _cast(g, dt)
    faulty = _count(masks, 0, n, dt) * _cast(g, dt)
    return _grid(_cast(n * g, dt), faulty[:, None], placed, dt)


def row_splice(masks, tps, g, dt, *, row_nodes: int, **_) -> Grid:
    """RailX: a row's healthy head and tail (before its first and after its
    last fault, or the whole fault-free row) splice into one global chain,
    carved into TP groups; nodes between two faults of a row strand."""
    rows, n = masks.shape
    count = n // row_nodes
    chain = np.zeros(rows, dtype=np.int64)
    for r in range(count):
        seg = masks[:, r * row_nodes:(r + 1) * row_nodes]
        has = seg.any(1)
        first = seg.argmax(1)
        last = row_nodes - 1 - seg[:, ::-1].argmax(1)
        chain += np.where(has, first + (row_nodes - 1 - last), row_nodes)
    chain = _cast(chain, dt)
    placed = np.stack([(chain // _cast(max(1, tp // g), dt))
                       * _cast(max(1, tp // g), dt) * _cast(g, dt)
                       for tp in tps], 1)
    faulty = _count(masks, 0, count * row_nodes, dt) * _cast(g, dt)
    return _grid(_cast(count * row_nodes * g, dt), faulty[:, None], placed, dt)


def rack_mesh(masks, tps, g, dt, *, mesh_gpus: int, **_) -> Grid:
    """UB-Mesh: racks of ``mesh_gpus`` in full mesh; a group up to a rack
    takes any healthy GPUs of one rack, a larger one whole fault-free
    racks."""
    rows, n = masks.shape
    per = mesh_gpus // g
    count = n // per
    f_gpus = _cast(masks[:, :count * per].reshape(rows, count, per).sum(2),
                   dt) * _cast(g, dt)
    avail = _cast(mesh_gpus, dt) - f_gpus
    whole = _cast((f_gpus == 0).sum(1), dt)
    placed = []
    for tp in tps:
        t = _cast(tp, dt)
        if tp <= mesh_gpus:
            placed.append(((avail // t) * t).sum(1, dtype=dt))
        else:
            placed.append((whole * _cast(mesh_gpus, dt) // t) * t)
    return _grid(_cast(count * mesh_gpus, dt), f_gpus.sum(1, dtype=dt)[:, None],
                 np.stack(placed, 1), dt)


def switch_arrays(masks, tps, g, dt, *, array_nodes: int, uplink_nodes: int,
                  **_) -> Grid:
    """ACOS: each array of ``array_nodes`` regroups freely; what an array
    cannot fill goes to a shared pool, at most ``uplink_nodes`` nodes' GPUs
    per array; groups larger than an array take any healthy GPU."""
    rows, n = masks.shape
    count = n // array_nodes
    h = (_cast(array_nodes, dt) - _cast(
        masks[:, :count * array_nodes].reshape(rows, count, array_nodes)
        .sum(2), dt)) * _cast(g, dt)
    cap = _cast(uplink_nodes * g, dt)
    placed = []
    for tp in tps:
        t = _cast(tp, dt)
        if tp <= array_nodes * g:
            q = (h // t) * t
            pool = np.minimum(h - q, cap).sum(1, dtype=dt)
            placed.append(q.sum(1, dtype=dt) + (pool // t) * t)
        else:
            placed.append((h.sum(1, dtype=dt) // t) * t)
    faulty = _count(masks, 0, count * array_nodes, dt) * _cast(g, dt)
    return _grid(_cast(count * array_nodes * g, dt), faulty[:, None],
                 np.stack(placed, 1), dt)


MODELS: Dict[str, Callable[..., Grid]] = {
    "big_switch": big_switch, "islands": islands, "cubes": cubes,
    "static_rings": static_rings, "khop_ring": khop_ring,
    "row_splice": row_splice, "rack_mesh": rack_mesh,
    "switch_arrays": switch_arrays,
}


def evaluate(architectures: Sequence[dict], masks: np.ndarray,
             tps: Sequence[int], gpus_per_node: int, dt=np.int64) -> Grid:
    """All architectures of a configuration: int64 ``(A, T)``, ``(A, rows,
    T)`` and ``(A, rows, T)`` grids, each computed in ``dt``.

    ``architectures`` are the configuration's entries: ``model`` names the
    placement rule above, the other keys are its parameters.
    """
    tps = [int(t) for t in tps]
    out = [MODELS[a["model"]](masks, tps, gpus_per_node, dt, **a)
           for a in architectures]
    return tuple(np.stack([o[i].astype(np.int64) for o in out])
                 for i in range(3))
