"""Shared grid and segment reductions for fault-sweep results.

One implementation of the mean/percentile/threshold reductions that both
``repro.sim.tables`` (SweepResult grids) and the ``*_batched`` wrappers in
``repro.core.fault_sim`` (per-model grids) apply -- previously duplicated in
both modules and pinned bit-for-bit to the scalar paths by
``tests/test_sim_engine.py``.  Keep the float conversions exactly as they
are: reordering them changes low bits and breaks the pinning.  The same
holds for the layout: :func:`waste_stats` and :func:`percentile_capacity`
take the snapshots on the first axis and reduce every trailing series in
one call, with the snapshot axis moved last and made contiguous -- a mean
along a contiguous last axis is NumPy's pairwise summation, as the 1-D
call's is, where a reduction over a non-contiguous axis sums naively and
changes low bits.  Percentiles keep the ``linear`` method.

Also home to the sparse *segment* reductions of the batched DCN placement
hot path (:func:`run_segments`, :func:`segment_carve_counts`): the K-hop
component decomposition of a fault-mask batch expressed over the nonzero
stream alone, shared by ``repro.dcn.kernel``'s carve counting and member
compaction so the two can never drift apart.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np


def _snapshots_last(series, dtype=None) -> np.ndarray:
    """``series`` (snapshots first) with the snapshot axis moved last and
    made contiguous; no copy where it already lies so."""
    return np.ascontiguousarray(np.moveaxis(np.asarray(series), 0, -1),
                                dtype=dtype)


def _percentiles(x: np.ndarray, q):
    """``linear`` percentiles ``q`` along ``x``'s last axis.  Sorting a
    copy first lets the percentile's partition run on sorted rows, which is
    faster and reads the same values."""
    return np.percentile(np.sort(x, axis=-1), q, axis=-1,
                         overwrite_input=True)


def _scalar(value):
    """A Python float for a 1-D series' 0-d result, the array otherwise."""
    return float(value) if np.ndim(value) == 0 else value


def waste_stats(series: np.ndarray) -> Tuple:
    """(mean, P50, P99) over snapshots of a waste-ratio series (Fig. 13/14
    reductions).  The first axis is snapshots; each result has the shape of
    the trailing axes, a Python float for a 1-D series."""
    x = _snapshots_last(series)
    p50, p99 = _percentiles(x, [50, 99])
    return tuple(_scalar(v) for v in (x.mean(axis=-1), p50, p99))


def percentile_capacity(placed: np.ndarray, percentile: float = 5.0):
    """Placeable-GPU percentile over snapshots -- P5 is the job scale a long
    run could hold through ~95% of the trace (Fig. 15).  The first axis is
    snapshots; the result has the shape of the trailing axes, a Python
    float for a 1-D series."""
    return _scalar(_percentiles(_snapshots_last(placed, float), percentile))


def waiting_share(placed: np.ndarray, job_gpus: int) -> float:
    """Share of snapshots during which a ``job_gpus`` job cannot run because
    placeable capacity < requirement (Fig. 16/23)."""
    placed = np.asarray(placed)
    if not len(placed):
        return 0.0
    return float((placed < job_gpus).sum() / len(placed))


# ------------------------------------------------------ segment reductions

def run_segments(avail: np.ndarray, max_gap: int
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Run decomposition of a ``(rows, cols)`` bool matrix's nonzero stream.

    Returns ``(rows32, cols32, starts, seg_len)``: the row-major nonzero
    coordinates (int32), the stream offset where each maximal run starts,
    and each run's length.  A run breaks at a row change or at a column
    gap of ``>= max_gap`` missing positions -- exactly Algorithm 2's K-hop
    component rule, O(nonzeros) past one ``np.nonzero``.
    """
    avail = np.asarray(avail, dtype=bool)
    rows, cols = np.nonzero(avail)        # row-major; cols ascend per row
    if not rows.size:
        e32 = np.zeros(0, dtype=np.int32)
        return e32, e32, e32, np.zeros(0, dtype=np.int32)
    rows32 = rows.astype(np.int32)
    cols32 = cols.astype(np.int32)
    new_seg = np.ones(rows.size, dtype=bool)
    new_seg[1:] = ((rows32[1:] != rows32[:-1])
                   | (cols32[1:] - cols32[:-1] - 1 >= max_gap))
    starts = np.flatnonzero(new_seg).astype(np.int32)
    seg_len = np.diff(np.append(starts, np.int32(rows.size)))
    return rows32, cols32, starts, seg_len


def segment_carve_counts(avail: np.ndarray, max_gap: int, m: int,
                         rows: int) -> np.ndarray:
    """Per-row carved-node counts: each run places ``len // m * m`` nodes
    (complete groups of ``m`` inside the component), summed per row into an
    int64 vector of length ``rows``."""
    rows32, _, starts, seg_len = run_segments(avail, max_gap)
    if not rows32.size:
        return np.zeros(rows, dtype=np.int64)
    return np.bincount(rows32[starts], weights=(seg_len // m) * m,
                       minlength=rows).astype(np.int64)


__all__ = ["waste_stats", "percentile_capacity", "waiting_share",
           "run_segments", "segment_carve_counts"]
