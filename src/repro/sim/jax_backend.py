"""JAX compute backend for the batched scenario engine.

Every HBD model's ``evaluate_batch`` kernel is re-expressed as a pure
``jax.numpy`` function over ONE snapshot mask, composed under ``jax.vmap``
over the snapshot axis and ``jax.jit`` over the whole (architectures x
snapshots x TP sizes) grid.  When the engine has several devices
(``repro.runtime.engine_devices``) the snapshot axis is sharded across them
with ``jax.shard_map``, so million-snapshot sweeps scale with the device
count.  :class:`GridEvaluator` runs one stream block by block, its blocks
device-resident and their input buffers donated, keeping peak memory at
~one block regardless of sweep size.

Each mask source of ``repro.sim.sources`` has its device half here, which
copies what the device needs of a block and builds its masks there:

  * :class:`HostMaskCopy` -- host masks, copied as they are;
  * :class:`CounterDraw` -- counter i.i.d. masks, each block's rows hashed
    by the repository's jnp threefry-2x32 (``repro.faults.jax_mirror``)
    over the explicit counter lanes of ``repro.core.prng.counter_lanes``
    (:func:`_draw_fn`), bit-identical to the host's
    ``repro.core.prng.counter_fault_masks`` whatever
    ``jax_threefry_partitionable`` says;
  * :class:`IntervalMaskSource` -- interval masks of a churn stream, built
    from the sorted occupancy deltas of
    ``repro.core.trace.interval_delta_blocks`` (:func:`_interval_fn`), the
    per-node counts carried on the device from block to block,
    bit-identical to ``FaultTrace.fault_mask_blocks``.

The built block goes into the grid program without leaving the device.

Guarantees (enforced by ``tests/test_jax_backend.py``):

  * bit-for-bit equality with the NumPy engine -- kernels compute in int32
    on device (all grid quantities fit comfortably) and are widened to the
    engine's int64 grids on the host;
  * deterministic results independent of block size and device count.
"""

from __future__ import annotations

import warnings
from typing import Callable, Dict, Iterator, Optional, Sequence, Tuple, Type

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
from ..core.trace import DeltaBlock
from ..faults.jax_mirror import threefry2x32_jnp
from ..runtime import engine_devices, snapshot_mesh
from ..core.hbd_models import (BigSwitch, HBDModel, InfiniteHBDModel,
                               NVLModel, SiPRingModel, TPUv4Model)

_SNAP_AXIS = "snap"


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


_INTERVAL_CACHE: Dict[Tuple, Callable] = {}


def _interval_fn(rows: int, width: int, mesh) -> Callable:
    """Jitted ``(block (rows, width) bool, carry (width,) int32, start
    int32, deltas (3, C) int32) -> (block', carry')`` build of interval
    masks from occupancy deltas (module ``jit_build_interval_masks``).

    ``deltas`` holds ``(row, node, step)`` entries of one
    ``repro.core.trace.DeltaBlock`` chunk (pads step 0).  Each row's count
    is ``carry`` plus the steps of the rows at or before it; ``block'`` is
    ``count > 0`` from row ``start`` on and ``block`` before it, and
    ``carry'`` is ``carry`` plus every step.  On a mesh each device builds
    its own rows: the deltas of the rows before them go into its base
    count, so no count crosses devices.  Counts are small integers, so the
    int32 cumsum is exact.
    """
    key = (rows, width, mesh)
    fn = _INTERVAL_CACHE.get(key)
    if fn is not None:
        return fn
    ndev = 1 if mesh is None else mesh.devices.size
    local = rows // ndev

    def build_interval_masks(block, carry, start, deltas):
        row, node, step = deltas[0], deltas[1], deltas[2]
        first = 0 if mesh is None else jax.lax.axis_index(_SNAP_AXIS) * local
        base = carry.at[node].add(jnp.where(row < first, step, 0))
        at = row - first
        mine = (at >= 0) & (at < local)
        # deltas of other devices' rows land on a scratch row, dropped
        steps = jnp.zeros((local + 1, width), jnp.int32).at[
            jnp.where(mine, at, local), node].add(step)
        counts = base + jnp.cumsum(steps[:local], axis=0)
        kept = (first + jnp.arange(local, dtype=jnp.int32))[:, None] < start
        return (jnp.where(kept, block, counts > 0),
                carry.at[node].add(step))

    build = build_interval_masks
    if mesh is not None:
        build = jax.shard_map(build, mesh=mesh,
                              in_specs=(P(_SNAP_AXIS), P(), P(), P()),
                              out_specs=(P(_SNAP_AXIS), P()))
    fn = jax.jit(build)
    _INTERVAL_CACHE[key] = fn
    return fn


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


def _pad(block: np.ndarray, rows: int) -> np.ndarray:
    """``block`` with zero rows appended up to ``rows`` (the tail block of
    a stream only)."""
    if block.shape[0] == rows:
        return block
    return np.concatenate([block, np.zeros((rows - block.shape[0],)
                                           + block.shape[1:], block.dtype)])


class HostMaskCopy:
    """Device half of ``repro.sim.sources.HostChunks``: each block's host
    masks, padded and copied to the devices as they are."""

    def __init__(self, mesh):
        self.sharding = (None if mesh is None
                         else NamedSharding(mesh, P(_SNAP_AXIS)))

    def put(self, block: np.ndarray, rows: int) -> Tuple[object, int]:
        block = _pad(block, rows)
        # one transfer straight into the sharded layout (device_put from
        # host numpy) -- no intermediate full copy on the default device
        return (jnp.asarray(block) if self.sharding is None
                else jax.device_put(block, self.sharding)), block.nbytes

    def build(self, block: np.ndarray, arg):
        return arg


class CounterDraw(HostMaskCopy):
    """Device half of ``repro.sim.sources.MaskGen``: each row's snapshot
    index folded into its threefry key on the host (8 B a row), the masks
    drawn on the device by :func:`_draw_fn`."""

    def __init__(self, gen, width: int, mesh):
        super().__init__(mesh)
        self.width = width
        self.draw = _draw_fn(width, mesh)
        self.root = cprng.threefry_seed(gen.seed)
        self.thresh = _threshold_args(gen.fault_ratio)

    def put(self, block: np.ndarray, rows: int) -> Tuple[object, int]:
        return super().put(
            cprng.threefry_fold_in_batch(self.root, _pad(block, rows)), rows)

    def build(self, block: np.ndarray, keys):
        with obs.span("prng.device_masks", samples=len(block),
                      nodes=self.width):
            masks = self.draw(keys, *self.thresh)
        obs.count("prng.device_masks_drawn", len(block))
        return masks


class IntervalMaskSource:
    """Device half of ``repro.sim.sources.IntervalDeltas``: builds the
    interval masks of one stream on the device, block by block, from
    ``repro.core.trace.DeltaBlock`` recipes.

    Every node's count is carried on the device from block to block,
    starting at zero, so nothing per node crosses the host link.  Each
    block comes out as a ``(rows, width)`` bool device array in the engine's
    snapshot sharding: ``rows`` rows whatever the block's length (the rows
    past it are not part of the stream), one compiled program
    (:func:`_interval_fn`) for every block and chunk.
    """

    def __init__(self, rows: int, width: int, mesh):
        ndev = 1 if mesh is None else mesh.devices.size
        if rows % ndev:
            raise ValueError(f"{rows} rows do not split over {ndev} devices")
        self.rows = rows
        self.width = width
        self.replicated = None if mesh is None else NamedSharding(mesh, P())
        self.fn = _interval_fn(rows, width, mesh)
        self.blank = jnp.zeros(
            (rows, width), bool,
            device=None if mesh is None else NamedSharding(mesh,
                                                           P(_SNAP_AXIS)))
        self.carry = jnp.zeros((width,), jnp.int32, device=self.replicated)

    def put(self, block: DeltaBlock, rows: int) -> Tuple[list, int]:
        """The block's chunks of deltas, copied to every device."""
        if block.rows > self.rows:
            raise ValueError(f"delta block of {block.rows} rows exceeds "
                             f"the source's {self.rows}")
        return ([jax.device_put(c, self.replicated) for c in block.chunks],
                block.chunks.nbytes)

    def build(self, block: DeltaBlock, chunks: list):
        """Dispatch the build of ``block`` from its :meth:`put` chunks; the
        counts carried out are kept for the next block."""
        with obs.span("churn.device_masks", rows=block.rows,
                      nodes=self.width):
            out = self.blank
            for start, deltas in zip(block.starts, chunks):
                out, self.carry = self.fn(out, self.carry, start, deltas)
        obs.count("churn.device_masks_built", block.rows)
        return out


class GridEvaluator:
    """Reusable device grid evaluator bound to one ``(models, tps, width)``
    and one mask source (``repro.sim.sources``).

    Holds the mesh, the jitted grid function, the source's device half and
    the zero-snapshot totals, so the engine's one loop
    (``repro.sim.engine.evaluate_source``) pushes block after block of a
    stream through one compiled executable with donated input buffers --
    device memory stays at ~one block no matter how many snapshots flow
    through, and the full mask matrix never exists on host or device.
    ``rows`` is the caller's block size: shorter blocks are padded up to
    it.
    """

    def __init__(self, models: Sequence[HBDModel], tps: Sequence[int],
                 width: int, *, source, rows: int = 1):
        require(models)
        self.models = list(models)
        self.tps = [int(t) for t in tps]
        self.mesh = snapshot_mesh(_SNAP_AXIS)
        self.ndev = 1 if self.mesh is None else self.mesh.devices.size
        # the block shape every shorter block is padded to: a stream whose
        # last block is short runs the executable its full blocks compiled
        self.rows = -(-max(1, rows) // self.ndev) * self.ndev
        self.fn = _grid_fn(self.models, self.tps, self.mesh, width)
        self.device_source = source.device(self.rows, width, self.mesh)

    def totals(self) -> np.ndarray:
        """Per-model (A, T) ``total_gpus`` grid (NumPy-engine identical)."""
        return _zero_snapshot_totals(self.models, self.tps)

    def eval_block(self, block) -> Tuple[np.ndarray, np.ndarray]:
        """Evaluate one block of the source; returns int64 ``(faulty,
        placed)``, each ``(A, len(block), T)``.

        Rows are padded on the tail to the evaluator's ``rows`` (a block
        longer than that to a device-count multiple) and the pad rows
        discarded, so every block of up to ``rows`` runs one compiled
        shape.

        Spans: ``sim.jax.eval_block`` around the whole block, inside it
        ``sim.jax.put`` (padding and the copy to the device of what the
        source's device half needs: the mask block, each row's folded
        threefry key, or the deltas), the source's build span
        (``prng.device_masks``, ``churn.device_masks``; none for host
        masks) and ``sim.jax.fetch`` (the copy back and the int64
        unpacking, after the program has finished).  The rest of
        ``eval_block`` is the wait for the transfer tail and the programs.
        The method takes no timings of its own.
        """
        rows = len(block)
        with obs.span("sim.jax.eval_block", rows=rows, devices=self.ndev):
            with obs.span("sim.jax.put", rows=rows) as sp:
                arg, nbytes = self.device_source.put(
                    block, max(self.rows, -(-rows // self.ndev) * self.ndev))
                sp.set(bytes=nbytes)
            arg = self.device_source.build(block, arg)
            with warnings.catch_warnings():
                # bool/int32 donation can't alias int32 outputs; the
                # donation still releases the block buffer eagerly, which
                # is the point
                warnings.filterwarnings("ignore", message=".*onat.*buffer.*")
                out = self.fn(arg)                 # (padded, A, 2, T)
            jax.block_until_ready(out)
            with obs.span("sim.jax.fetch", rows=rows):
                out = np.asarray(out)
                return (out[:rows, :, 0].transpose(1, 0, 2).astype(np.int64),
                        out[:rows, :, 1].transpose(1, 0, 2).astype(np.int64))


def device_masks(source, rows: int) -> Iterator[np.ndarray]:
    """Every block of ``source`` (``repro.sim.sources``, blocks of up to
    ``rows`` rows) as its device half builds it over the engine's devices,
    returned as host bool matrices (for tests and tools): bit-identical to
    the source's ``host_masks``."""
    if not HAVE_JAX:
        raise RuntimeError(f"jax unavailable ({_IMPORT_ERROR!r})")
    mesh = snapshot_mesh(_SNAP_AXIS)
    ndev = 1 if mesh is None else mesh.devices.size
    padded = -(-max(1, rows) // ndev) * ndev
    half = None
    for block in source.blocks(rows):
        if half is None:
            half = source.device(padded, source.width, mesh)
        arg, _ = half.put(block, padded)
        yield np.asarray(half.build(block, arg))[:len(block)]


def num_devices() -> int:
    return len(engine_devices()) if HAVE_JAX else 0


__all__ = [
    "HAVE_JAX", "CounterDraw", "GridEvaluator", "HostMaskCopy",
    "IntervalMaskSource", "available_for", "device_masks", "num_devices",
    "require",
]
