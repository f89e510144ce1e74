"""Batched sweep runner: evaluate a ScenarioSpec grid block by block.

Reproduces the paper's §6.2 fault-resiliency figures (Figs. 13-16: waste
ratio, max job scale, fault-waiting share) at grid scale; the churn
(Fig. 18), traffic (Fig. 17) and cost (§6.5) engines all consume the
grids it produces.

Every evaluation is one loop, :func:`evaluate_source`, over a mask source
(``repro.sim.sources``): host bool matrices (:func:`evaluate_masks`,
:func:`evaluate_mask_stream`), the counter i.i.d. stream of a
``CounterIIDSnapshots`` spec, or a churn stream's interval deltas.  The
loop runs every architecture's kernel over each block of the source's
snapshot axis, so datacenter-scale sweeps (100k nodes x millions of
snapshots) stay within a bounded memory footprint, and fills a dense
``(architectures, snapshots, tp_sizes)`` grid that the table helpers
reduce to the paper's figures.

Two compute backends produce that grid bit-for-bit identically:

  * ``backend="numpy"`` -- each block's host masks through the vectorized
    ``evaluate_batch`` kernel of each model;
  * ``backend="jax"``   -- ``repro.sim.jax_backend``: one
    ``GridEvaluator`` per stream runs the same kernels as pure
    ``jax.numpy`` functions under ``jax.vmap``/``jax.jit`` with the
    snapshot axis sharded across devices; the source's device half builds
    each block's masks on the device where it can.

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
from ..obs.progress import Progress, StreamProgress
from .scenario import CounterIIDSnapshots, ScenarioSpec
from .sources import HostChunks, MaskGen

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
        """``(healthy - placed) / total`` as ``(A, S, T)``, 0 where an
        architecture has no GPUs.  Laid out snapshot axis last, so each
        (architecture, TP) series is contiguous for the table reductions."""
        wasted = np.moveaxis(self.healthy_gpus - self.placed_gpus, 1, -1)
        total = np.broadcast_to(self.total_gpus[:, :, None], wasted.shape)
        waste = np.divide(wasted, total, out=np.zeros(wasted.shape),
                          where=total != 0)
        return np.moveaxis(waste, -1, 1)

    def index(self, name: str) -> int:
        return self.names.index(name)

    def tp_index(self, tp: int) -> int:
        return int(np.nonzero(self.tp_sizes == tp)[0][0])


Grids = Tuple[np.ndarray, np.ndarray, np.ndarray, str]


def evaluate_source(models: Sequence[HBDModel], tp_sizes: Sequence[int],
                    source, total_snapshots: int, *,
                    chunk_snapshots: int = 1024, backend: str = "auto",
                    progress: Optional[Callable[[Progress], None]] = None
                    ) -> Grids:
    """Evaluate every block of a mask ``source`` (``repro.sim.sources``)
    whose rows add up to ``total_snapshots``.

    The source cuts its stream into blocks of ``chunk_snapshots`` rows
    (only the last may be shorter).  On NumPy each block's host masks go
    through every model's batched kernel; on JAX one
    ``repro.sim.jax_backend.GridEvaluator``, made at the first block under
    ``sim.jax.setup``, takes every block, builds its masks where the
    source's device half says and pads the short last block up to the
    others, so the stream runs one compiled shape.  Returns int64
    ``(total (A, T), faulty (A, S, T), placed (A, S, T), backend)`` grids,
    bit-for-bit identical across backends and block sizes.

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
    rows = max(1, chunk_snapshots)
    tracker = StreamProgress(total_snapshots, progress, prefix="sim.stream")
    evaluator, lo = None, 0
    with obs.span("sim.evaluate_source", backend=chosen,
                  snapshots=total_snapshots, models=a_count):
        for block in source.blocks(rows):
            n = len(block)
            if chosen == "jax" and evaluator is None:
                from . import jax_backend
                with obs.span("sim.jax.setup", rows=total_snapshots):
                    evaluator = jax_backend.GridEvaluator(
                        models, tp_sizes, source.width, source=source,
                        rows=min(rows, total_snapshots))
                    total[:] = evaluator.totals()
            with obs.span("sim.stream.block", rows=n, offset=lo,
                          backend=chosen):
                obs.count("sim.snapshots_evaluated", n)
                if evaluator is not None:
                    f, p = evaluator.eval_block(block)
                    faulty[:, lo:lo + n], placed[:, lo:lo + n] = f, p
                else:
                    masks = source.host_masks(block)
                    with obs.span("sim.numpy.eval_chunk", rows=n):
                        for ai, model in enumerate(models):
                            grid = model.evaluate_batch(masks, tp_sizes)
                            total[ai] = grid.total_gpus
                            faulty[ai, lo:lo + n] = grid.faulty_gpus
                            placed[ai, lo:lo + n] = grid.placed_gpus
            lo += n
            tracker.update(n)
    if lo != total_snapshots:
        raise ValueError(f"mask stream yielded {lo} snapshots, "
                         f"expected {total_snapshots}")
    return total, faulty, placed, chosen


def evaluate_masks(models: Sequence[HBDModel], tp_sizes: Sequence[int],
                   masks: np.ndarray, *, chunk_snapshots: int = 1024,
                   backend: str = "auto") -> Grids:
    """Evaluate a pre-materialized ``(snapshots, nodes)`` mask matrix: the
    :func:`evaluate_source` of one host chunk, shared by :func:`run_sweep`
    and the churn replay engine (``repro.churn``)."""
    masks = np.asarray(masks, dtype=bool)
    return evaluate_source(models, tp_sizes, HostChunks([masks]),
                           masks.shape[0], chunk_snapshots=chunk_snapshots,
                           backend=backend)


def evaluate_mask_stream(models: Sequence[HBDModel], tp_sizes: Sequence[int],
                         chunks: Iterable[np.ndarray], total_snapshots: int,
                         *, chunk_snapshots: int = 1024,
                         backend: str = "auto",
                         progress: Optional[Callable[[Progress], None]] = None
                         ) -> Grids:
    """Evaluate a *stream* of mask chunks in bounded memory.

    ``chunks`` is any iterable of ``(rows_i, nodes)`` bool matrices whose
    rows concatenate to ``total_snapshots`` snapshots, re-cut into blocks
    of ``chunk_snapshots`` rows (``repro.sim.sources.HostChunks``), so the
    grids are bit-for-bit equal to one :func:`evaluate_masks` call on the
    full concatenation while a million-snapshot x 10k-node stream never
    exists as a 10 GB host matrix.  ``progress`` is that of
    :func:`evaluate_source`.
    """
    return evaluate_source(models, tp_sizes, HostChunks(chunks),
                           total_snapshots, chunk_snapshots=chunk_snapshots,
                           backend=backend, progress=progress)


def run_sweep(spec: ScenarioSpec, *, masks: Optional[np.ndarray] = None,
              models: Optional[Sequence[HBDModel]] = None,
              chunk_snapshots: int = 1024,
              backend: str = "auto") -> SweepResult:
    """Evaluate the full scenario grid.

    ``masks``/``models`` may be supplied to reuse an already-materialized
    snapshot matrix or model instances (the benchmarks do both so timing
    isolates the kernels).  Without ``masks``, a ``CounterIIDSnapshots``
    spec streams its masks block by block (``repro.sim.sources.MaskGen``:
    drawn on the device on JAX, regenerated per block from its start
    offset on NumPy), so a million-snapshot spec never materializes the
    full matrix.  ``backend`` selects the compute path (see the module
    docstring); the grids are bit-for-bit identical either way.
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
        sn = spec.snapshots
        if masks is None and isinstance(sn, CounterIIDSnapshots):
            grids = evaluate_source(
                models, spec.tp_sizes,
                MaskGen(sn.samples, spec.num_nodes, sn.fault_ratio, sn.seed),
                sn.samples, chunk_snapshots=chunk_snapshots, backend=chosen)
        else:
            grids = evaluate_masks(
                models, spec.tp_sizes,
                sn.masks(spec.num_nodes) if masks is None else masks,
                chunk_snapshots=chunk_snapshots, backend=chosen)
        return SweepResult(spec, names, tps, *grids)


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
