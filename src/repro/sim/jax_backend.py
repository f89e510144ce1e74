"""JAX compute backend for the batched scenario engine.

Every HBD model's ``evaluate_batch`` kernel is re-expressed as a pure
``jax.numpy`` function over ONE snapshot mask, composed under ``jax.vmap``
over the snapshot axis and ``jax.jit`` over the whole (architectures x
snapshots x TP sizes) grid.  When the engine has several devices
(``repro.runtime.engine_devices``) the snapshot axis is sharded across them
with ``jax.shard_map``, so million-snapshot sweeps scale with the device
count.  Chunks are device-resident and their input buffers donated,
keeping peak memory at ~one chunk regardless of sweep size.

Guarantees (enforced by ``tests/test_jax_backend.py``):

  * bit-for-bit equality with the NumPy engine -- kernels compute in int32
    on device (all grid quantities fit comfortably) and are widened to the
    engine's int64 grids on the host;
  * deterministic results independent of chunking and device count;
  * counter i.i.d. fault masks are drawn on the device: each block's rows
    are hashed by the repository's jnp threefry-2x32
    (``repro.faults.jax_mirror``) over the explicit counter lanes of
    ``repro.core.prng.counter_lanes``, bit-identical to the host's
    ``repro.core.prng.counter_fault_masks`` whatever
    ``jax_threefry_partitionable`` says, and the block goes from the draw
    into the grid program without leaving the device.  Every other mask
    source is drawn on the host and copied over block by block.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Callable, Dict, Optional, Sequence, Tuple, Type

import numpy as np

try:  # keep repro.sim importable on numpy-only installs
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    HAVE_JAX = True
    _IMPORT_ERROR: Optional[BaseException] = None
except Exception as e:  # pragma: no cover - exercised on jax-free installs
    HAVE_JAX = False
    _IMPORT_ERROR = e

from .. import obs
from ..core import prng as cprng
from ..faults.jax_mirror import threefry2x32_jnp
from ..runtime import engine_devices, snapshot_mesh
from ..core.hbd_models import (BigSwitch, HBDModel, InfiniteHBDModel,
                               NVLModel, SiPRingModel, TPUv4Model)

_SNAP_AXIS = "snap"


@dataclasses.dataclass(frozen=True)
class MaskGen:
    """A counter i.i.d. mask source drawn on the device (no host matrix):
    rows ``0..samples-1`` of ``counter_fault_masks(num_nodes, fault_ratio,
    samples, seed)``."""

    samples: int
    num_nodes: int
    fault_ratio: float
    seed: int


# ---------------------------------------------------------------- kernels
# Each builder returns fn(mask: (W,) bool) -> (faulty (T,), placed (T,))
# in int32, where W is the raw mask width; the kernel itself clips/pads to
# the model's node count exactly like HBDModel._clip_masks.

def _clip(mask, n: int):
    w = mask.shape[0]
    if w == n:
        return mask
    if w > n:
        return mask[:n]
    return jnp.concatenate([mask, jnp.zeros(n - w, bool)])


def _bigswitch_kernel(model: BigSwitch, tps: Sequence[int]):
    n, g, total = model.num_nodes, model.gpus_per_node, model.total_gpus
    tps_a = np.asarray(tps, np.int32)

    def fn(mask):
        m = _clip(mask, n)
        faulty = m.sum(dtype=jnp.int32) * g
        placed = ((total - faulty) // tps_a) * tps_a
        return jnp.broadcast_to(faulty, placed.shape), placed
    return fn


def _infinitehbd_kernel(model: InfiniteHBDModel, tps: Sequence[int]):
    n, g, k = model.num_nodes, model.gpus_per_node, model.k
    closed = model.closed_ring
    ms = [max(1, int(tp) // g) for tp in tps]

    def fn(mask):
        m = _clip(mask, n)
        # the cumsums deliberately stay jnp.cumsum: swapping in the blocked
        # GEMM form (repro.kernels.prefix_scan) measured ~10% SLOWER here on
        # XLA CPU -- the cummax/cummin component scans below dominate and
        # have no matmul formulation, so the extra padding/reshape traffic
        # never pays for itself
        # a gap of >= K consecutive faults splits the K-hop line; runk marks
        # every completion of such a run (the component boundaries)
        cs = jnp.concatenate([jnp.zeros(1, jnp.int32),
                              jnp.cumsum(m.astype(jnp.int32))])
        runk = jnp.zeros(n, bool)
        if n >= k:
            runk = runk.at[k - 1:].set((cs[k:] - cs[:n - k + 1]) == k)
        healthy = ~m
        # scan-only component sizing (no scatter/searchsorted, which XLA CPU
        # serializes): for each node, the healthy-prefix count at its
        # component's start (forward cummax over boundary-tagged prefixes)
        # and end (reverse cummin) give its in-component rank and size
        hc0 = jnp.concatenate([jnp.zeros(1, jnp.int32),
                               jnp.cumsum(healthy.astype(jnp.int32))])
        before = hc0[:n]                        # healthy strictly before i
        comp_start = jax.lax.cummax(jnp.where(runk, before, 0))
        comp_end = jax.lax.cummin(jnp.where(runk, before, hc0[n]),
                                  reverse=True)
        rank = before - comp_start
        size = comp_end - comp_start
        if closed:
            # wrap merge: first and last components join when the
            # wrap-around fault gap is shorter than K
            cid = jnp.cumsum(runk.astype(jnp.int32))
            any_h = healthy.any()
            first_h = jnp.argmax(healthy)
            last_h = n - 1 - jnp.argmax(healthy[::-1])
            s_first, s_last = size[first_h], size[last_h]
            wrap_gap = first_h + n - last_h - 1
            merge = any_h & (cid[first_h] != cid[last_h]) & (wrap_gap < k)
        placed = []
        for mm in ms:
            # node is placed iff its m-block completes within the component
            nodes = (healthy
                     & (rank - rank % mm + mm <= size)).sum(dtype=jnp.int32)
            if closed:
                delta = (((s_first + s_last) // mm) * mm
                         - (s_first // mm) * mm - (s_last // mm) * mm)
                nodes = nodes + jnp.where(merge, delta, 0)
            placed.append(nodes * g)
        placed = jnp.stack(placed)
        return jnp.broadcast_to(cs[-1] * g, placed.shape), placed
    return fn


def _nvl_kernel(model: NVLModel, tps: Sequence[int]):
    g = model.gpus_per_node
    npn = model.hbd_gpus // g
    n_hbd = model.num_nodes // npn
    spares = int(round(model.hbd_gpus * model.spare_fraction))
    compute = model.hbd_gpus - spares
    tps_a = np.asarray(tps, np.int32)

    def fn(mask):
        m = _clip(mask, model.num_nodes)
        isle = m[:n_hbd * npn].reshape(n_hbd, npn)
        f_gpus = isle.sum(axis=1, dtype=jnp.int32) * g
        avail = jnp.maximum(compute - jnp.maximum(f_gpus - spares, 0), 0)
        placed = ((avail[:, None] // tps_a) * tps_a).sum(axis=0)
        return jnp.broadcast_to(f_gpus.sum(), placed.shape), placed
    return fn


def _tpuv4_kernel(model: TPUv4Model, tps: Sequence[int]):
    g = model.gpus_per_node
    npc = model.cube_gpus // g
    n_cubes = model.num_nodes // npc
    n = model.num_nodes

    def fn(mask):
        m = _clip(mask, n)
        cube = m[:n_cubes * npc].reshape(n_cubes, npc)
        faulty = cube.sum(dtype=jnp.int32) * g
        healthy_cubes = (~cube.any(axis=1)).sum(dtype=jnp.int32)
        placed = []
        for tp in tps:
            tp = int(tp)
            if tp <= model.cube_gpus:
                # static sub-block id grid; tail blocks may overrun into the
                # neighbor cube (same quirk as the NumPy path) -- clip at N
                bn = max(1, tp // g)
                starts = np.arange(0, npc, bn)
                ids = (np.arange(n_cubes)[:, None, None] * npc
                       + starts[None, :, None]
                       + np.arange(bn)[None, None, :])
                in_range = ids < n
                f = m[np.minimum(ids, max(n - 1, 0))] & in_range
                placed.append((~f.any(axis=2)).sum(dtype=jnp.int32) * tp)
            else:
                placed.append((healthy_cubes * model.cube_gpus // tp) * tp)
        placed = jnp.stack(placed)
        return jnp.broadcast_to(faulty, placed.shape), placed
    return fn


def _sipring_kernel(model: SiPRingModel, tps: Sequence[int]):
    g, n = model.gpus_per_node, model.num_nodes

    def fn(mask):
        m = _clip(mask, n)
        faulty, placed = [], []
        for tp in tps:
            tp = int(tp)
            npr = max(1, tp // g)
            n_rings = n // npr
            rings = m[:n_rings * npr].reshape(n_rings, npr)
            placed.append((~rings.any(axis=1)).sum(dtype=jnp.int32) * tp)
            faulty.append(rings.sum(dtype=jnp.int32) * g)
        return jnp.stack(faulty), jnp.stack(placed)
    return fn


_KERNELS: Dict[Type[HBDModel], Callable] = {
    BigSwitch: _bigswitch_kernel,
    InfiniteHBDModel: _infinitehbd_kernel,
    NVLModel: _nvl_kernel,
    TPUv4Model: _tpuv4_kernel,
    SiPRingModel: _sipring_kernel,
}


def _builder_for(model: HBDModel) -> Optional[Callable]:
    """Kernel builder of one model: the type-keyed builtin table first,
    then the model's ``repro.core.arch`` spec (external architectures ship
    their builder in ``ArchSpec.jax_kernel``)."""
    builder = _KERNELS.get(type(model))
    if builder is None:
        from ..core import arch
        spec = arch.find(model.name)
        builder = spec.jax_kernel if spec is not None else None
    return builder


def _model_key(model: HBDModel) -> Tuple:
    """Static identity of a model's compiled kernel (for the jit cache):
    the model's own ``static_key`` (type name + geometry + the subclass's
    ``_static_config`` knobs)."""
    return model.static_key()


def available_for(models: Sequence[HBDModel]) -> bool:
    """True when JAX is importable and every model has a jnp kernel."""
    return HAVE_JAX and all(_builder_for(m) is not None for m in models)


def require(models: Sequence[HBDModel]) -> None:
    if not HAVE_JAX:
        raise RuntimeError(
            f"backend='jax' requested but jax is unavailable ({_IMPORT_ERROR!r})")
    missing = [m.name for m in models if _builder_for(m) is None]
    if missing:
        raise RuntimeError(
            f"backend='jax' has no kernel for model(s) {missing}; "
            f"use backend='numpy' or register an ArchSpec.jax_kernel")


# ------------------------------------------------------------- grid runner

_DRAW_CACHE: Dict[Tuple, Callable] = {}


def _draw_fn(width: int, mesh) -> Callable:
    """Jitted ``(keys (rows, 2) uint32, thresh uint32, full bool) ->
    (rows, width) bool`` draw of counter-stream fault masks.

    Row ``i`` hashes the lanes of ``repro.core.prng.counter_lanes`` under
    its own key ``keys[i]`` (``fold_in(seed_key, snapshot_index)``); a node
    is faulty where its bits are below ``thresh``, or everywhere when
    ``full`` (a threshold of ``2**32``, which uint32 cannot hold).  Seed
    and ratio are arguments, never part of the cache key, so every spec of
    one width runs one executable (module ``jit_draw_counter_masks``).
    """
    key = (width, mesh)
    fn = _DRAW_CACHE.get(key)
    if fn is not None:
        return fn
    c0, c1 = cprng.counter_lanes(width)
    half = c0.size

    def draw_counter_masks(keys, thresh, full):
        x0, x1 = threefry2x32_jnp(keys[:, :1], keys[:, 1:], c0, c1)
        # compared before they are joined: the cipher's fusion writes
        # bools, not uint32 bits
        return jnp.concatenate([x0 < thresh, x1[:, :width - half] < thresh],
                               axis=1) | full

    draw = draw_counter_masks
    if mesh is not None:
        draw = jax.shard_map(draw, mesh=mesh,
                             in_specs=(P(_SNAP_AXIS), P(), P()),
                             out_specs=P(_SNAP_AXIS))
    fn = jax.jit(draw)
    _DRAW_CACHE[key] = fn
    return fn


def _threshold_args(ratio: float) -> Tuple[np.uint32, np.bool_]:
    """``(thresh, full)`` arguments of the draw for one fault ratio."""
    thresh = cprng.ratio_threshold(ratio)
    return np.uint32(min(thresh, 0xFFFFFFFF)), np.bool_(thresh >= 1 << 32)


_GRID_CACHE: Dict[Tuple, Callable] = {}


def _grid_fn(models: Sequence[HBDModel], tps: Sequence[int], mesh,
             width: int) -> Callable:
    """Jitted ``(rows, W) bool -> (rows, A, 2, T) int32`` grid evaluator
    (module ``jit_eval_mask``), its mask argument donated.

    Cached on the models' static configuration so repeated sweeps (and the
    benchmark's warm-up + timed call) reuse one compiled executable.
    """
    key = (tuple(_model_key(m) for m in models),
           tuple(int(t) for t in tps), width, mesh)
    fn = _GRID_CACHE.get(key)
    if fn is not None:
        obs.count("sim.jax.jit_cache_hit")
        return fn
    obs.count("sim.jax.jit_cache_miss")

    kernels = [(m.name, _builder_for(m)(m, tps)) for m in models]

    def eval_mask(mask):
        # each kernel's ops carry its architecture's name as their scope
        # in the compiled program (and so in a device trace)
        out = []
        for name, kfn in kernels:
            with jax.named_scope(name):
                out.append(jnp.stack(kfn(mask)))
        return jnp.stack(out)

    batched = jax.vmap(eval_mask)
    if mesh is not None:
        batched = jax.shard_map(batched, mesh=mesh, in_specs=P(_SNAP_AXIS),
                                out_specs=P(_SNAP_AXIS))
    fn = jax.jit(batched, donate_argnums=0)
    _GRID_CACHE[key] = fn
    return fn


def _zero_snapshot_totals(models: Sequence[HBDModel],
                          tps: Sequence[int]) -> np.ndarray:
    """Per-model ``total_gpus`` rows, from the NumPy kernels on an empty
    snapshot batch -- guaranteed identical to the NumPy engine's totals."""
    return np.stack([
        np.asarray(m.evaluate_batch(np.zeros((0, m.num_nodes), bool),
                                    tps).total_gpus, dtype=np.int64)
        for m in models])


class GridEvaluator:
    """Reusable device grid evaluator bound to one ``(models, tps, width)``.

    Holds the mesh, sharding, jitted grid function and zero-snapshot totals
    so a *streaming* caller can push chunk after chunk through one compiled
    executable with donated input buffers -- device memory stays at ~one
    chunk no matter how many snapshots flow through.  :func:`sweep_grids`
    is a loop over :meth:`eval_block`; ``repro.sim.engine``'s
    ``evaluate_mask_stream`` drives one evaluator across an entire mask
    stream (million-snapshot Monte-Carlo) without ever materializing the
    full matrix on host or device.  ``rows`` is the caller's block size:
    shorter blocks are padded up to it.
    """

    def __init__(self, models: Sequence[HBDModel], tps: Sequence[int],
                 width: int, gen: Optional[MaskGen] = None, rows: int = 1):
        require(models)
        self.models = list(models)
        self.tps = [int(t) for t in tps]
        self.width = width
        self.gen = gen
        self.mesh = snapshot_mesh(_SNAP_AXIS)
        self.ndev = 1 if self.mesh is None else self.mesh.devices.size
        # the block shape every shorter block is padded to: a stream whose
        # last block is short runs the executable its full blocks compiled
        self.rows = -(-max(1, rows) // self.ndev) * self.ndev
        self.sharding = (None if self.mesh is None
                         else NamedSharding(self.mesh, P(_SNAP_AXIS)))
        self.fn = _grid_fn(self.models, self.tps, self.mesh, width)
        if gen is not None:
            self.draw = _draw_fn(width, self.mesh)
            self.root = cprng.threefry_seed(gen.seed)
            self.thresh = _threshold_args(gen.fault_ratio)

    def totals(self) -> np.ndarray:
        """Per-model (A, T) ``total_gpus`` grid (NumPy-engine identical)."""
        return _zero_snapshot_totals(self.models, self.tps)

    def eval_block(self, block: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Evaluate one block; returns int64 ``(faulty, placed)``, each
        ``(A, rows, T)``.

        ``block`` is a ``(rows, width)`` bool mask matrix -- or, when the
        evaluator was built with ``gen``, a ``(rows,)`` integer vector of
        counter-stream snapshot indices, whose masks are drawn on the
        device.  Rows are padded on the tail to the evaluator's ``rows``
        (a block longer than that to a device-count multiple) and the pad
        rows discarded, so every block of up to ``rows`` runs one compiled
        shape.

        Spans: ``sim.jax.eval_block`` around the whole block, inside it
        ``sim.jax.put`` (padding and the copy to the device: the mask
        block, or each row's threefry key folded on the host),
        ``prng.device_masks`` (the dispatch of the device draw, ``gen``
        only) and ``sim.jax.fetch`` (the copy back and the int64
        unpacking, after the program has finished).  The rest of
        ``eval_block`` is the wait for the transfer tail and the programs.
        The method takes no timings of its own.
        """
        rows = block.shape[0]
        with obs.span("sim.jax.eval_block", rows=rows, devices=self.ndev):
            with obs.span("sim.jax.put", rows=rows) as sp:
                padded = max(self.rows, -(-rows // self.ndev) * self.ndev)
                if padded != rows:             # pad the tail chunk only
                    block = np.concatenate(
                        [block, np.zeros((padded - rows,) + block.shape[1:],
                                         block.dtype)])
                if self.gen is not None:
                    block = cprng.threefry_fold_in_batch(self.root, block)
                sp.set(bytes=block.nbytes)
                # one transfer straight into the sharded layout (device_put
                # from host numpy) -- no intermediate full copy on the
                # default device
                arg = (jnp.asarray(block) if self.sharding is None
                       else jax.device_put(block, self.sharding))
            if self.gen is not None:
                with obs.span("prng.device_masks", samples=rows,
                              nodes=self.width):
                    arg = self.draw(arg, *self.thresh)
                obs.count("prng.device_masks_drawn", rows)
            with warnings.catch_warnings():
                # bool/int32 donation can't alias int32 outputs; the
                # donation still releases the chunk buffer eagerly, which
                # is the point
                warnings.filterwarnings("ignore", message=".*onat.*buffer.*")
                out = self.fn(arg)                 # (padded, A, 2, T)
            jax.block_until_ready(out)
            with obs.span("sim.jax.fetch", rows=rows):
                out = np.asarray(out)
                return (out[:rows, :, 0].transpose(1, 0, 2).astype(np.int64),
                        out[:rows, :, 1].transpose(1, 0, 2).astype(np.int64))


def sweep_grids(models: Sequence[HBDModel], tps: Sequence[int], *,
                masks: Optional[np.ndarray] = None,
                gen: Optional[MaskGen] = None,
                chunk_snapshots: int = 1024) -> Tuple[np.ndarray, np.ndarray,
                                                      np.ndarray]:
    """Evaluate the grid on device; returns int64 (total, faulty, placed).

    Exactly one of ``masks`` (host snapshot matrix) and ``gen``
    (counter masks drawn on the device, block by block) must be provided.
    """
    if (masks is None) == (gen is None):
        raise ValueError("provide exactly one of masks= and gen=")
    if masks is not None:
        masks = np.asarray(masks, dtype=bool)
        snaps, width = masks.shape
    else:
        snaps, width = gen.samples, gen.num_nodes

    a_count, t_count = len(models), len(tps)
    total = np.zeros((a_count, t_count), dtype=np.int64)
    faulty = np.zeros((a_count, snaps, t_count), dtype=np.int64)
    placed = np.zeros((a_count, snaps, t_count), dtype=np.int64)
    if snaps == 0:  # NumPy engine's zero-snapshot grid keeps totals at zero
        return total, faulty, placed

    chunk = max(1, chunk_snapshots)
    with obs.span("sim.jax.setup", rows=snaps):
        ev = GridEvaluator(models, tps, width, gen=gen,
                           rows=min(chunk, snaps))
        total[:] = ev.totals()
    for lo in range(0, snaps, ev.rows):
        hi = min(lo + ev.rows, snaps)
        block = (masks[lo:hi] if masks is not None
                 else np.arange(lo, hi, dtype=np.int64))
        f, p = ev.eval_block(block)
        faulty[:, lo:hi] = f
        placed[:, lo:hi] = p
    return total, faulty, placed


def counter_masks_device(gen: MaskGen, start: int = 0) -> np.ndarray:
    """Rows ``start..start+samples-1`` of the counter stream, drawn on the
    device by the sweep's own draw program and returned as a host bool
    matrix (for tests and tools).  Bit-identical to
    ``repro.core.prng.counter_fault_masks(..., start=start)``."""
    if not HAVE_JAX:
        raise RuntimeError(f"jax unavailable ({_IMPORT_ERROR!r})")
    if gen.samples == 0 or gen.num_nodes == 0:
        return np.zeros((gen.samples, gen.num_nodes), bool)
    keys = cprng.threefry_fold_in_batch(
        cprng.threefry_seed(gen.seed),
        np.arange(start, start + gen.samples, dtype=np.int64))
    draw = _draw_fn(gen.num_nodes, None)
    return np.asarray(draw(jnp.asarray(keys),
                           *_threshold_args(gen.fault_ratio)))


def num_devices() -> int:
    return len(engine_devices()) if HAVE_JAX else 0


__all__ = [
    "HAVE_JAX", "GridEvaluator", "MaskGen", "available_for", "require",
    "sweep_grids", "counter_masks_device", "num_devices",
]
