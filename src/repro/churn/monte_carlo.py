"""Monte-Carlo churn: many independent trace realizations, one batched pass.

A :class:`ChurnSpec` is the declarative seed of a cluster-lifetime
experiment (Appendix-A trace statistics + the sweep grid); realization
``r`` regenerates bit-identically from ``seed + r``.  The Monte-Carlo
layer concatenates every realization's per-interval occupancy masks along
the scenario engine's snapshot axis and evaluates the whole ensemble in
one ``evaluate_masks`` call -- on the JAX backend that means thousands of
348-day traces stream through the device-sharded `vmap`/`jit` grid in
seconds, bit-for-bit equal to the scalar event-by-event replay
(``benchmarks/churn.py`` gates the >= 10x throughput claim).  For
horizons or ensembles too large to concatenate, ``engine="streamed"``
treats the realizations as one stream of intervals in blocks, with the
same bit-for-bit grids in bounded memory (``tests/test_stream.py``): on
the JAX backend each block goes to the device as its sorted occupancy
deltas and its masks are built there (``evaluate_delta_stream``); on
NumPy each realization's masks are built block by block on the host and
re-chunked through ``evaluate_mask_stream``.
"""

from __future__ import annotations

import dataclasses
from typing import (Callable, Dict, Iterator, List, Optional, Sequence,
                    Tuple, Union)

import numpy as np

from .. import obs
from ..core.trace import (DeltaBlock, FaultTrace, generate_trace,
                          interval_delta_blocks, to_4gpu_trace)
from ..obs.progress import Progress
from ..sim.engine import (evaluate_delta_stream, evaluate_mask_stream,
                          evaluate_masks, resolve_backend)
from ..sim.scenario import DEFAULT_ARCHITECTURES, make_model
from .replay import replay_trace
from .timeline import ChurnTimeline


@dataclasses.dataclass(frozen=True)
class ChurnSpec:
    """One cluster-lifetime experiment: trace statistics x sweep grid."""

    trace_nodes: int                 # 8-GPU nodes fed to the Appendix-A generator
    horizon_h: float = 348 * 24.0
    convert_4gpu: bool = True        # Appendix-A Bayes split to 4-GPU nodes
    tp_sizes: Tuple[int, ...] = (32,)
    architectures: Tuple[str, ...] = DEFAULT_ARCHITECTURES
    gpus_per_node: int = 4
    mean_repair_h: float = 8.0
    seed: int = 0

    @property
    def num_nodes(self) -> int:
        return self.trace_nodes * 2 if self.convert_4gpu else self.trace_nodes

    def trace(self, realization: int = 0) -> FaultTrace:
        """Trace realization ``r`` (deterministic in ``seed + r``)."""
        s = self.seed + realization
        tr = generate_trace(self.trace_nodes, horizon_h=self.horizon_h,
                            mean_repair_h=self.mean_repair_h, seed=s)
        return to_4gpu_trace(tr, seed=s) if self.convert_4gpu else tr

    def models(self):
        return [make_model(a, self.num_nodes, self.gpus_per_node)
                for a in self.architectures]


@dataclasses.dataclass
class ChurnEnsemble:
    """Per-realization timelines of one Monte-Carlo churn run."""

    spec: ChurnSpec
    timelines: List[ChurnTimeline]
    backend: str

    @property
    def num_traces(self) -> int:
        return len(self.timelines)

    def _empty_grid(self) -> np.ndarray:
        return np.zeros((0, len(self.spec.architectures),
                         len(self.spec.tp_sizes)))

    def integrated_waste(self) -> np.ndarray:
        """Time-integrated waste ratio per realization, ``(R, A, T)``."""
        if not self.timelines:
            return self._empty_grid()
        return np.stack([tl.integrated_waste_ratio() for tl in self.timelines])

    def placed_share(self) -> np.ndarray:
        """Goodput share of total GPU-hours per realization, ``(R, A, T)``."""
        if not self.timelines:
            return self._empty_grid()
        return np.stack([tl.placed_share() for tl in self.timelines])

    def summary_table(self) -> List[Dict]:
        """Per (architecture, TP): waste/goodput stats across realizations."""
        if not self.timelines:
            return []
        with obs.span("churn.tables", realizations=self.num_traces):
            waste = self.integrated_waste()
            share = self.placed_share()
            rows = []
            tl0 = self.timelines[0]
            for ai, name in enumerate(tl0.names):
                for ti, tp in enumerate(tl0.tp_sizes):
                    w = waste[:, ai, ti]
                    rows.append({
                        "architecture": name, "tp_size": int(tp),
                        "traces": self.num_traces,
                        "mean_waste": float(w.mean()),
                        "p99_waste": float(np.percentile(w, 99)),
                        "mean_placed_share": float(share[:, ai, ti].mean()),
                    })
        return rows


def _realizations(spec: ChurnSpec,
                  traces: Union[int, Sequence[FaultTrace]]
                  ) -> List[Tuple[FaultTrace, np.ndarray]]:
    """``(trace, interval edges)`` of every realization, each made under a
    ``churn.trace`` span (generation and 4-GPU split when ``traces`` is a
    count, then the interval edges)."""
    count = traces if isinstance(traces, int) else len(traces)
    out = []
    for r in range(count):
        with obs.span("churn.trace", realization=r) as sp:
            tr = spec.trace(r) if isinstance(traces, int) else traces[r]
            edges = tr.interval_edges()
            sp.set(events=len(tr.events), intervals=len(edges))
        obs.count("churn.fault_events", len(tr.events))
        obs.count("churn.intervals", len(edges))
        out.append((tr, edges))
    return out


def _mask_blocks(realizations: Sequence[Tuple[FaultTrace, np.ndarray]],
                 rows: int) -> Iterator[np.ndarray]:
    """Every realization's interval masks in turn, ``rows`` intervals per
    block, each block built under a ``churn.masks`` span."""
    for tr, edges in realizations:
        blocks = tr.fault_mask_blocks(edges, rows)
        for lo in range(0, len(edges), rows):
            with obs.span("churn.masks", rows=min(rows, len(edges) - lo),
                          nodes=tr.num_nodes):
                masks = next(blocks)
            yield masks


def _delta_blocks(realizations: Sequence[Tuple[FaultTrace, np.ndarray]],
                  rows: int, nodes: int) -> Iterator[DeltaBlock]:
    """Every realization's interval masks in turn, as one stream of
    occupancy-delta recipes of ``rows`` intervals each (the device builds
    the masks), each sliced and padded under a ``churn.masks`` span."""
    total = sum(len(edges) for _, edges in realizations)
    blocks = interval_delta_blocks(realizations, rows)
    for lo in range(0, total, rows):
        with obs.span("churn.masks", rows=min(rows, total - lo), nodes=nodes):
            block = next(blocks)
        yield block


def monte_carlo_replay(spec: ChurnSpec,
                       traces: Union[int, Sequence[FaultTrace]], *,
                       engine: str = "batched", backend: str = "auto",
                       chunk_snapshots: int = 4096,
                       progress: Optional[Callable[[Progress], None]] = None
                       ) -> ChurnEnsemble:
    """Replay ``traces`` realizations of ``spec`` into a :class:`ChurnEnsemble`.

    ``traces`` is a count (realizations ``0..traces-1`` are generated) or a
    pre-generated sequence of :class:`FaultTrace` (the benchmarks pass one
    so engine timing excludes trace generation).  ``engine="batched"``
    evaluates ALL realizations' interval masks in a single scenario-engine
    pass; ``engine="streamed"`` produces bit-identical timelines in blocks
    of ``chunk_snapshots`` intervals of the realizations' joint stream,
    bounding peak mask memory at about one block whatever the horizon or
    the ensemble size.  On the JAX backend each block's masks are built on
    the device from its occupancy deltas
    (``repro.core.trace.interval_delta_blocks``, ``evaluate_delta_stream``);
    on NumPy each realization's masks are built on the host
    (``FaultTrace.fault_mask_blocks``) and fed through
    ``evaluate_mask_stream``, re-chunked across realization boundaries.
    ``engine="scalar"`` loops the event-by-event reference replay.

    ``progress`` (``engine="streamed"`` only) is forwarded to
    ``evaluate_mask_stream`` -- one :class:`repro.obs.Progress` per
    evaluated block; the default publishes ``sim.stream.*`` telemetry
    gauges (blocks done, snapshots/sec, ETA).
    """
    if engine not in ("batched", "streamed", "scalar"):
        raise ValueError(f"unknown engine {engine!r} (batched|streamed|scalar)")
    realizations = _realizations(spec, traces)
    if engine == "scalar":
        tls = [replay_trace(tr, tp_sizes=spec.tp_sizes,
                            architectures=spec.architectures,
                            gpus_per_node=spec.gpus_per_node, engine="scalar")
               for tr, _ in realizations]
        return ChurnEnsemble(spec, tls, "scalar")

    models = spec.models()
    names = [m.name for m in models]
    tps = np.asarray(spec.tp_sizes, dtype=np.int64)
    with obs.span("churn.monte_carlo_replay", engine=engine,
                  realizations=len(realizations)):
        intervals = int(sum(len(e) for _, e in realizations))
        if engine == "streamed":
            chunk_snapshots = max(1, chunk_snapshots)
            if resolve_backend(backend, models) == "jax":
                width = (realizations[0][0].num_nodes if realizations
                         else spec.num_nodes)
                total, faulty, placed, chosen = evaluate_delta_stream(
                    models, spec.tp_sizes, width,
                    _delta_blocks(realizations, chunk_snapshots, width),
                    intervals, chunk_snapshots=chunk_snapshots,
                    progress=progress)
            else:
                total, faulty, placed, chosen = evaluate_mask_stream(
                    models, spec.tp_sizes,
                    _mask_blocks(realizations, chunk_snapshots), intervals,
                    chunk_snapshots=chunk_snapshots, backend=backend,
                    progress=progress)
        else:
            if realizations:
                masks = np.concatenate([tr.fault_masks(e)
                                        for tr, e in realizations])
            else:
                masks = np.zeros((0, spec.num_nodes), dtype=bool)
            total, faulty, placed, chosen = evaluate_masks(
                models, spec.tp_sizes, masks,
                chunk_snapshots=chunk_snapshots, backend=backend)

    with obs.span("churn.tables", realizations=len(realizations)):
        tls = []
        lo = 0
        for tr, edges in realizations:
            hi = lo + len(edges)
            tls.append(ChurnTimeline(tr.horizon_h, edges, list(names), tps,
                                     total.copy(), faulty[:, lo:hi].copy(),
                                     placed[:, lo:hi].copy(), backend=chosen))
            lo = hi
    return ChurnEnsemble(spec, tls, chosen)


__all__ = ["ChurnEnsemble", "ChurnSpec", "monte_carlo_replay"]
