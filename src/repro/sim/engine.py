"""Batched sweep runner: evaluate a ScenarioSpec grid in vectorized chunks.

Reproduces the paper's §6.2 fault-resiliency figures (Figs. 13-16: waste
ratio, max job scale, fault-waiting share) at grid scale; the churn
(Fig. 18), traffic (Fig. 17) and cost (§6.5) engines all consume the
grids it produces.

The engine materializes the snapshot fault-mask matrix once, then runs every
architecture's vectorized ``evaluate_batch`` kernel over it, chunking the
snapshot axis so datacenter-scale sweeps (100k nodes x thousands of
snapshots) stay within a bounded memory footprint.  Results land in a dense
``(architectures, snapshots, tp_sizes)`` grid that the table helpers reduce
to the paper's figures.

Two compute backends produce that grid bit-for-bit identically:

  * ``backend="numpy"`` -- the vectorized host kernels on each model;
  * ``backend="jax"``   -- ``repro.sim.jax_backend``: the same kernels as
    pure ``jax.numpy`` functions under ``jax.vmap``/``jax.jit`` with the
    snapshot axis sharded across devices (million-snapshot sweeps); a
    ``CounterIIDSnapshots`` spec has its masks drawn on the device too.

``backend="auto"`` (the default) picks JAX whenever it is importable and
every requested architecture has a jnp kernel; the ``REPRO_SWEEP_BACKEND``
environment variable overrides the auto choice (CI runs the matrix both
ways).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Callable, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from .. import obs
from ..core.hbd_models import HBDModel
from ..core.prng import counter_fault_masks
from ..obs.progress import Progress, StreamProgress
from .scenario import CounterIIDSnapshots, ScenarioSpec

BACKENDS = ("numpy", "jax")


def resolve_backend(backend: Optional[str],
                    models: Sequence[HBDModel]) -> str:
    """Resolve ``backend`` ("auto"/None reads ``REPRO_SWEEP_BACKEND``).

    An explicit ``backend="jax"`` raises when JAX (or a model kernel) is
    missing.  ``REPRO_SWEEP_BACKEND=jax`` also raises when JAX itself is
    unavailable (so a broken install can't silently green-light the CI jax
    matrix leg on NumPy), but still falls back per-call for models without
    a jnp kernel.
    """
    if backend in (None, "auto"):
        backend = os.environ.get("REPRO_SWEEP_BACKEND", "auto").strip().lower() \
            or "auto"
        if backend not in ("auto",) + BACKENDS:
            raise ValueError(
                f"REPRO_SWEEP_BACKEND={backend!r} (want numpy|jax|auto)")
        if backend in ("auto", "jax"):
            from . import jax_backend
            if backend == "jax" and not jax_backend.HAVE_JAX:
                raise RuntimeError(
                    "REPRO_SWEEP_BACKEND=jax but jax is unavailable")
            return "jax" if jax_backend.available_for(models) else "numpy"
        return backend
    if backend == "jax":
        from . import jax_backend
        jax_backend.require(models)
        return "jax"
    if backend == "numpy":
        return "numpy"
    raise ValueError(f"unknown backend {backend!r} (numpy|jax|auto)")


@dataclasses.dataclass
class SweepResult:
    """Dense result grid of one scenario sweep.

    Grid axes are ``(architectures A, snapshots S, TP sizes T)`` for the
    per-snapshot counts; ``total_gpus`` is ``(A, T)`` because TP-granular
    models round the modeled cluster to whole groups.  ``backend`` records
    which compute path produced the grids -- they are bit-for-bit
    identical either way.
    """

    spec: ScenarioSpec
    names: List[str]         # architecture names, grid axis 0
    tp_sizes: np.ndarray     # (T,), grid axis 2
    total_gpus: np.ndarray   # (A, T)
    faulty_gpus: np.ndarray  # (A, S, T)
    placed_gpus: np.ndarray  # (A, S, T)
    backend: str = "numpy"   # compute backend that produced the grid

    @property
    def num_snapshots(self) -> int:
        return self.placed_gpus.shape[1]

    @property
    def healthy_gpus(self) -> np.ndarray:
        return self.total_gpus[:, None, :] - self.faulty_gpus

    @property
    def waste_ratio(self) -> np.ndarray:
        total = np.broadcast_to(self.total_gpus[:, None, :],
                                self.placed_gpus.shape)
        return np.divide(self.healthy_gpus - self.placed_gpus, total,
                         out=np.zeros(self.placed_gpus.shape),
                         where=total != 0)

    def index(self, name: str) -> int:
        return self.names.index(name)

    def tp_index(self, tp: int) -> int:
        return int(np.nonzero(self.tp_sizes == tp)[0][0])


def evaluate_masks(models: Sequence[HBDModel], tp_sizes: Sequence[int],
                   masks: np.ndarray, *, chunk_snapshots: int = 1024,
                   backend: str = "auto") -> Tuple[np.ndarray, np.ndarray,
                                                   np.ndarray, str]:
    """Evaluate a pre-materialized ``(snapshots, nodes)`` mask matrix.

    The mask-in/grids-out core shared by :func:`run_sweep` and the churn
    replay engine (``repro.churn``): every model's batched kernel over every
    snapshot x TP cell, chunked along the snapshot axis.  Returns int64
    ``(total (A, T), faulty (A, S, T), placed (A, S, T), backend)`` grids,
    bit-for-bit identical across backends.
    """
    chosen = resolve_backend(backend, models)
    masks = np.asarray(masks, dtype=bool)
    tp_sizes = list(tp_sizes)

    with obs.span("sim.evaluate_masks", backend=chosen,
                  snapshots=masks.shape[0], models=len(models)):
        obs.count("sim.snapshots_evaluated", masks.shape[0])
        if chosen == "jax":
            from . import jax_backend
            total, faulty, placed = jax_backend.sweep_grids(
                models, tp_sizes, masks=masks,
                chunk_snapshots=chunk_snapshots)
            return total, faulty, placed, "jax"

        snaps = masks.shape[0]
        tcount = len(tp_sizes)
        total = np.zeros((len(models), tcount), dtype=np.int64)
        faulty = np.zeros((len(models), snaps, tcount), dtype=np.int64)
        placed = np.zeros((len(models), snaps, tcount), dtype=np.int64)
        chunk_snapshots = max(1, chunk_snapshots)  # same clamp as the jax path
        for lo in range(0, max(snaps, 1), chunk_snapshots):
            chunk = masks[lo:lo + chunk_snapshots]
            if not chunk.shape[0]:
                break
            with obs.span("sim.numpy.eval_chunk", rows=chunk.shape[0]):
                for ai, model in enumerate(models):
                    grid = model.evaluate_batch(chunk, tp_sizes)
                    total[ai] = grid.total_gpus
                    faulty[ai, lo:lo + chunk.shape[0]] = grid.faulty_gpus
                    placed[ai, lo:lo + chunk.shape[0]] = grid.placed_gpus
    return total, faulty, placed, "numpy"


def evaluate_mask_stream(models: Sequence[HBDModel], tp_sizes: Sequence[int],
                         chunks: Iterable[np.ndarray], total_snapshots: int,
                         *, chunk_snapshots: int = 1024,
                         backend: str = "auto",
                         progress: Optional[Callable[[Progress], None]] = None
                         ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, str]:
    """Evaluate a *stream* of mask chunks in bounded memory.

    ``chunks`` is any iterable of ``(rows_i, nodes)`` bool matrices whose
    rows concatenate to ``total_snapshots`` snapshots.  Incoming chunks are
    re-chunked into evaluation blocks of exactly ``chunk_snapshots`` rows
    (chunk boundaries in the source need not align with evaluation
    boundaries; only the stream's last block may be shorter), so the grids
    are bit-for-bit equal to one :func:`evaluate_masks` call on the full
    concatenation while peak mask memory stays at about one block plus the
    largest single source chunk -- a million-snapshot x 10k-node stream
    never exists as a 10 GB host matrix.  On the JAX backend every block
    flows through one ``repro.sim.jax_backend.GridEvaluator`` sized to the
    block, which pads the short last block up to it: however the stream's
    length falls, it runs one compiled shape, with donated device buffers.

    ``progress`` is called once per evaluated block with a
    :class:`repro.obs.Progress` (blocks done, snapshots/sec, ETA); the
    default publishes the same numbers as telemetry gauges under
    ``sim.stream.*`` -- a no-op unless telemetry is enabled -- so
    multi-minute streaming runs are never silent.
    """
    chosen = resolve_backend(backend, models)
    tp_sizes = list(tp_sizes)
    a_count, t_count = len(models), len(tp_sizes)
    total = np.zeros((a_count, t_count), dtype=np.int64)
    faulty = np.zeros((a_count, total_snapshots, t_count), dtype=np.int64)
    placed = np.zeros((a_count, total_snapshots, t_count), dtype=np.int64)
    chunk_snapshots = max(1, chunk_snapshots)
    state = {"lo": 0, "evaluator": None}
    pending: List[np.ndarray] = []
    tracker = StreamProgress(total_snapshots, progress, prefix="sim.stream")

    def evaluate(block: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        if chosen != "jax":
            t, f, p, _ = evaluate_masks(models, tp_sizes, block,
                                        chunk_snapshots=chunk_snapshots,
                                        backend=chosen)
            total[:] = t
            return f, p
        if state["evaluator"] is None:
            from . import jax_backend
            with obs.span("sim.jax.setup", rows=total_snapshots):
                state["evaluator"] = jax_backend.GridEvaluator(
                    models, tp_sizes, block.shape[1],
                    rows=min(chunk_snapshots, total_snapshots))
                total[:] = state["evaluator"].totals()
        obs.count("sim.snapshots_evaluated", block.shape[0])
        return state["evaluator"].eval_block(block)

    def flush(block: np.ndarray) -> None:
        lo = state["lo"]
        with obs.span("sim.stream.block", rows=block.shape[0], offset=lo,
                      backend=chosen):
            f, p = evaluate(block)
        faulty[:, lo:lo + block.shape[0]] = f
        placed[:, lo:lo + block.shape[0]] = p
        state["lo"] = lo + block.shape[0]
        tracker.update(block.shape[0])

    with obs.span("sim.evaluate_mask_stream", backend=chosen,
                  snapshots=total_snapshots):
        for chunk in chunks:
            chunk = np.asarray(chunk, dtype=bool)
            if not chunk.shape[0]:
                continue
            pending.append(chunk)
            if sum(c.shape[0] for c in pending) < chunk_snapshots:
                continue
            rows = pending[0] if len(pending) == 1 else np.concatenate(pending)
            del pending[:]
            full = rows.shape[0] - rows.shape[0] % chunk_snapshots
            for lo in range(0, full, chunk_snapshots):
                flush(rows[lo:lo + chunk_snapshots])
            if full < rows.shape[0]:        # a copy frees the source
                pending.append(rows[full:].copy())
        if pending:
            flush(pending[0] if len(pending) == 1 else np.concatenate(pending))
    if state["lo"] != total_snapshots:
        raise ValueError(f"mask stream yielded {state['lo']} snapshots, "
                         f"expected {total_snapshots}")
    return total, faulty, placed, chosen


def run_sweep(spec: ScenarioSpec, *, masks: Optional[np.ndarray] = None,
              models: Optional[Sequence[HBDModel]] = None,
              chunk_snapshots: int = 1024,
              backend: str = "auto") -> SweepResult:
    """Evaluate the full scenario grid.

    ``masks``/``models`` may be supplied to reuse an already-materialized
    snapshot matrix or model instances (the benchmarks do both so timing
    isolates the kernels).  ``backend`` selects the compute path (see the
    module docstring); the grids are bit-for-bit identical either way.
    """
    if models is None:
        with obs.span("sim.models") as sp:
            models = spec.models()
            sp.set(models=len(models))
    names = [m.name for m in models]
    tps = np.asarray(spec.tp_sizes, dtype=np.int64)
    chosen = resolve_backend(backend, models)

    with obs.span("sim.run_sweep", backend=chosen, nodes=spec.num_nodes,
                  models=len(models)):
        if chosen == "jax" and masks is None \
                and isinstance(spec.snapshots, CounterIIDSnapshots):
            # counter-based spec: each block's masks are drawn on the
            # device (bit-identical to the host stream) and never leave it
            from . import jax_backend
            sn = spec.snapshots
            obs.count("sim.snapshots_evaluated", sn.samples)
            gen = jax_backend.MaskGen(sn.samples, spec.num_nodes,
                                      sn.fault_ratio, sn.seed)
            total, faulty, placed = jax_backend.sweep_grids(
                models, spec.tp_sizes, gen=gen,
                chunk_snapshots=chunk_snapshots)
            return SweepResult(spec, names, tps, total, faulty, placed,
                               backend="jax")

        if masks is None:
            if isinstance(spec.snapshots, CounterIIDSnapshots):
                # counter streams regenerate any row range bit-identically
                # from a start offset, so stream the masks chunk by chunk --
                # a million-snapshot spec never materializes the full host
                # matrix
                sn = spec.snapshots
                step = max(1, chunk_snapshots)
                chunks = (counter_fault_masks(spec.num_nodes, sn.fault_ratio,
                                              min(step, sn.samples - off),
                                              sn.seed, start=off)
                          for off in range(0, sn.samples, step))
                total, faulty, placed, chosen = evaluate_mask_stream(
                    models, spec.tp_sizes, chunks, sn.samples,
                    chunk_snapshots=chunk_snapshots, backend=chosen)
                return SweepResult(spec, names, tps, total, faulty, placed,
                                   backend=chosen)
            masks = spec.snapshots.masks(spec.num_nodes)
        total, faulty, placed, chosen = evaluate_masks(
            models, spec.tp_sizes, masks, chunk_snapshots=chunk_snapshots,
            backend=chosen)
        return SweepResult(spec, names, tps, total, faulty, placed,
                           backend=chosen)


def run_sweep_scalar(spec: ScenarioSpec, *,
                     masks: Optional[np.ndarray] = None,
                     models: Optional[Sequence[HBDModel]] = None) -> SweepResult:
    """Reference implementation: loop the scalar ``evaluate`` path.

    Exists for equivalence testing (``tests/test_sim_engine.py``).  The
    ``sweep`` benchmark times its own historical scalar loop -- the seed
    benchmarks' per-instant ``faulty_at`` extraction included -- so its
    baseline covers mask materialization too, not just the kernels.
    """
    if masks is None:
        masks = spec.snapshots.masks(spec.num_nodes)
    masks = np.asarray(masks, dtype=bool)
    if models is None:
        models = spec.models()
    snaps = masks.shape[0]
    tcount = len(spec.tp_sizes)
    total = np.zeros((len(models), tcount), dtype=np.int64)
    faulty = np.zeros((len(models), snaps, tcount), dtype=np.int64)
    placed = np.zeros((len(models), snaps, tcount), dtype=np.int64)
    for ai, model in enumerate(models):
        clipped = masks[:, :model.num_nodes]
        for si in range(snaps):
            faults = set(np.nonzero(clipped[si])[0].tolist())
            for ti, tp in enumerate(spec.tp_sizes):
                r = model.evaluate(faults, int(tp))
                total[ai, ti] = r.total_gpus
                faulty[ai, si, ti] = r.faulty_gpus
                placed[ai, si, ti] = r.placed_gpus
    return SweepResult(spec, [m.name for m in models],
                       np.asarray(spec.tp_sizes, dtype=np.int64),
                       total, faulty, placed)
