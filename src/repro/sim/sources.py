"""Mask sources: where the fault masks of a stream's blocks come from.

The engine's one evaluation loop (``repro.sim.engine.evaluate_source``)
walks a source block by block; a source knows three things:

  * ``blocks(rows)`` -- the stream cut into blocks of at most ``rows``
    rows (``len(block)`` rows each), and ``width``, the stream's node
    count, once its first block is out;
  * ``host_masks(block)`` -- the block's ``(len(block), width)`` bool
    masks on the host (the NumPy backend, and tests);
  * ``device(rows, width, mesh)`` -- its device half in
    ``repro.sim.jax_backend``: ``put(block, rows)`` copies what the device
    needs of a block padded to ``rows`` rows and returns it with its byte
    count, ``build(block, arg)`` dispatches the masks' build under the
    source's own span.

Every source streams bit-identical masks on both halves.  This module
imports no JAX: the device halves are imported when asked for.
"""

from __future__ import annotations

import dataclasses
from typing import Iterable, Iterator, Optional, Sequence, Tuple

import numpy as np

from .. import obs
from ..core.prng import counter_fault_masks
from ..core.trace import (DELTA_CAPACITY, DeltaBlock, FaultTrace,
                          interval_delta_blocks)


class HostChunks:
    """Host bool matrices: ``chunks`` is any iterable of ``(rows_i,
    nodes)`` matrices, re-cut into blocks of exactly ``rows`` rows (only
    the stream's last may be shorter; chunk boundaries need not align with
    blocks), so peak mask memory is about one block plus the largest
    chunk.  The device half copies each block as it is."""

    def __init__(self, chunks: Iterable[np.ndarray]):
        self.chunks = chunks
        self.width: Optional[int] = None

    def blocks(self, rows: int) -> Iterator[np.ndarray]:
        pending = []
        for chunk in self.chunks:
            chunk = np.asarray(chunk, dtype=bool)
            if not chunk.shape[0]:
                continue
            self.width = chunk.shape[1]
            pending.append(chunk)
            if sum(c.shape[0] for c in pending) < rows:
                continue
            masks = pending[0] if len(pending) == 1 else np.concatenate(pending)
            del pending[:]
            full = masks.shape[0] - masks.shape[0] % rows
            for lo in range(0, full, rows):
                yield masks[lo:lo + rows]
            if full < masks.shape[0]:          # a copy frees the source
                pending.append(masks[full:].copy())
        if pending:
            yield pending[0] if len(pending) == 1 else np.concatenate(pending)

    @staticmethod
    def host_masks(block: np.ndarray) -> np.ndarray:
        return block

    def device(self, rows: int, width: int, mesh):
        from .jax_backend import HostMaskCopy
        return HostMaskCopy(mesh)


@dataclasses.dataclass(frozen=True)
class MaskGen:
    """Counter i.i.d. masks: rows ``start..start+samples-1`` of
    ``counter_fault_masks(num_nodes, fault_ratio, ..., seed)``.  A block is
    its vector of snapshot indices; the device half draws its masks on the
    device (``repro.sim.jax_backend.CounterDraw``)."""

    samples: int
    num_nodes: int
    fault_ratio: float
    seed: int
    start: int = 0

    @property
    def width(self) -> int:
        return self.num_nodes

    def blocks(self, rows: int) -> Iterator[np.ndarray]:
        end = self.start + self.samples
        for lo in range(self.start, end, rows):
            yield np.arange(lo, min(lo + rows, end), dtype=np.int64)

    def host_masks(self, block: np.ndarray) -> np.ndarray:
        return counter_fault_masks(self.num_nodes, self.fault_ratio,
                                   len(block), self.seed,
                                   start=int(block[0]))

    def device(self, rows: int, width: int, mesh):
        from .jax_backend import CounterDraw
        return CounterDraw(self, width, mesh)


class IntervalDeltas:
    """Interval masks of ``(trace, sample times)`` pieces in turn, as one
    stream: each block is the :class:`repro.core.trace.DeltaBlock` of its
    sorted occupancy deltas (``interval_delta_blocks``, ``capacity``
    deltas per chunk), sliced under a ``churn.masks`` span (rows, nodes).
    Both halves carry every node's count from block to block: the host
    half as int16 counts, the device half
    (``repro.sim.jax_backend.IntervalMaskSource``) on the device."""

    def __init__(self, pieces: Sequence[Tuple[FaultTrace, Sequence[float]]],
                 width: int, capacity: int = DELTA_CAPACITY):
        self.pieces = pieces
        self.width = width
        self.capacity = capacity

    def blocks(self, rows: int) -> Iterator[DeltaBlock]:
        self.carry = np.zeros(self.width, dtype=np.int16)
        total = sum(len(ts) for _, ts in self.pieces)
        recipes = interval_delta_blocks(self.pieces, rows, self.capacity)
        for lo in range(0, total, rows):
            with obs.span("churn.masks", rows=min(rows, total - lo),
                          nodes=self.width):
                block = next(recipes)
            yield block

    def host_masks(self, block: DeltaBlock) -> np.ndarray:
        out, self.carry = block.host_masks(self.width, self.carry)
        return out

    def device(self, rows: int, width: int, mesh):
        from .jax_backend import IntervalMaskSource
        return IntervalMaskSource(rows, width, mesh)


__all__ = ["HostChunks", "IntervalDeltas", "MaskGen"]
