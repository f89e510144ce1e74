"""JAX backend for the batched fat-tree placement kernel.

The Algorithm-4/5 pipeline of :mod:`repro.dcn.kernel` re-expressed as a
pure ``jax.numpy`` function of ONE snapshot mask -- masked tier carves,
count-vector binary search (``fori_loop`` with a static trip count),
scatter/lexsort materialization -- composed under ``jax.vmap`` over the
snapshot axis and ``jax.jit`` over the grid, with the snapshot axis
sharded across the engine's devices via ``jax.shard_map`` (same layout
and device choice as ``repro.sim.jax_backend``).

The device kernel emits the placement *member* grid; DP-ring pair counting
happens on the host through the identical ``kernel.batched_pair_counts``
code path both backends share, so traffic counts can only disagree if the
placements themselves do -- and placement equality is pinned bit-for-bit
by ``tests/test_dcn.py``.  All device arithmetic is int32 (node ids fit
comfortably) and widened to int64 on the host.
"""

from __future__ import annotations

import warnings
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

try:  # keep repro.dcn importable on numpy-only installs
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P
    HAVE_JAX = True
    _IMPORT_ERROR: Optional[BaseException] = None
except Exception as e:  # pragma: no cover - exercised on jax-free installs
    HAVE_JAX = False
    _IMPORT_ERROR = e

from .. import obs
from ..runtime import engine_devices, snapshot_mesh
from .kernel import BatchedPlacement, FatTreeConfig

_SNAP_AXIS = "snap"


def require() -> None:
    if not HAVE_JAX:
        raise RuntimeError(
            f"backend='jax' requested but jax is unavailable ({_IMPORT_ERROR!r})")


def num_devices() -> int:
    return len(engine_devices()) if HAVE_JAX else 0


# ---------------------------------------------------------------- kernel

def _carve(f, k: int, m: int):
    """:func:`repro.dcn.kernel.line_carve` in jnp along the last axis."""
    length = f.shape[-1]
    healthy = ~f
    hc = jnp.cumsum(healthy, axis=-1, dtype=jnp.int32)
    before = hc - healthy                      # exclusive healthy prefix
    total = hc[..., -1:]
    if length >= k:
        zeros = jnp.zeros(f.shape[:-1] + (1,), jnp.int32)
        fc0 = jnp.concatenate(
            [zeros, jnp.cumsum(f, axis=-1, dtype=jnp.int32)], axis=-1)
        runk = jnp.concatenate(
            [jnp.zeros(f.shape[:-1] + (k - 1,), bool),
             (fc0[..., k:] - fc0[..., :length - k + 1]) == k], axis=-1)
    else:
        runk = jnp.zeros(f.shape, bool)
    axis = f.ndim - 1
    comp_start = lax.cummax(jnp.where(runk, before, 0), axis=axis)
    comp_end = lax.cummin(jnp.where(runk, before, total), axis=axis,
                          reverse=True)
    rank = before - comp_start
    size = comp_end - comp_start
    return healthy & (rank - rank % m + m <= size)


def _snapshot_fn(cfg: FatTreeConfig, tp_sizes: Sequence[int],
                 job_gpus: Sequence[int]) -> Callable:
    """Build ``mask (n,) bool -> [per-tp {members, feasible, n_constraints}]``."""
    n, p = cfg.num_nodes, cfg.nodes_per_tor
    agg, d, tpd, k = cfg.agg_domain, cfg.n_domains, cfg.tors_per_domain, cfg.k
    order = jnp.asarray(cfg.order(), dtype=jnp.int32)
    high = cfg.max_constraints
    iters = high.bit_length() + 1
    d_idx = jnp.arange(d, dtype=jnp.int32)[:, None, None]
    i_idx = jnp.arange(p, dtype=jnp.int32)[None, :, None]
    t_idx = jnp.arange(tpd, dtype=jnp.int32)[None, None, :]
    node_of = d_idx * agg + t_idx * p + i_idx           # (D, P, Tpd)

    # the name is the program's: ``jit_place_fat_tree`` in a device trace
    def place_fat_tree(mask):
        grid = mask[:d * tpd * p].reshape(d, tpd, p)
        raw = grid.transpose(0, 2, 1)                   # (D, P, Tpd)
        aligned = jnp.broadcast_to(grid.any(axis=2, keepdims=True),
                                   grid.shape).transpose(0, 2, 1)
        out = []
        for tp, job in zip(tp_sizes, job_gpus):
            m = cfg.group_nodes(int(tp))
            need = cfg.need_groups(int(tp), int(job))

            def tier_placed(c):
                n_sub = jnp.minimum(c, p)
                n_align = jnp.clip(c - p, 0, d)
                eff = jnp.where((jnp.arange(d) < n_align)[:, None, None],
                                aligned, raw)
                placed = _carve(eff, k, m)
                return placed & (jnp.arange(p) < n_sub)[None, :, None]

            def scheme(c):
                placed_tier = tier_placed(c)
                used = placed_tier.transpose(0, 2, 1).reshape(n)
                placed_res = _carve((mask | used)[order], k, m)
                return placed_tier, placed_res

            def counts(c):
                placed_tier, placed_res = scheme(c)
                return (placed_tier.sum(dtype=jnp.int32) // m
                        + placed_res.sum(dtype=jnp.int32) // m)

            def body(_, st):
                lo, hi, best = st
                active = lo <= hi
                mid = (lo + hi) // 2
                feas = active & (counts(mid) >= need)
                return (jnp.where(feas, mid + 1, lo),
                        jnp.where(active & ~feas, mid - 1, hi),
                        jnp.where(feas, mid, best))

            lo0 = jnp.int32(0)
            _, _, best = lax.fori_loop(
                0, iters, body, (lo0, jnp.int32(high), jnp.int32(-1)))
            feasible = best >= 0

            placed_tier, placed_res = scheme(jnp.maximum(best, 0))
            g_max = tpd // m
            slots = d * p * g_max
            rs = n // m

            if slots:
                pc = (jnp.cumsum(placed_tier, axis=-1, dtype=jnp.int32)
                      - placed_tier)
                gid = jnp.where(placed_tier, pc // m, g_max)    # OOB: drop
                tier_nodes = jnp.full((d, p, g_max, m), -1, jnp.int32)
                tier_nodes = tier_nodes.at[
                    jnp.broadcast_to(d_idx, placed_tier.shape),
                    jnp.broadcast_to(i_idx, placed_tier.shape),
                    gid, pc % m].set(
                        jnp.broadcast_to(node_of, placed_tier.shape),
                        mode="drop")
                flat = tier_nodes.reshape(slots, m)
                valid = flat[:, 0] >= 0
                sig = jnp.where(flat >= 0, flat // p, n)
                dom_k = jnp.where(
                    valid, jnp.repeat(jnp.arange(d, dtype=jnp.int32),
                                      p * g_max), d)
                pos_k = jnp.tile(jnp.arange(g_max, dtype=jnp.int32), d * p)
                idx_k = jnp.tile(
                    jnp.repeat(jnp.arange(p, dtype=jnp.int32), g_max), d)
                keys = (idx_k, pos_k) + tuple(
                    sig[:, r] for r in range(m - 1, -1, -1)) + (dom_k,)
                tier_sorted = flat[jnp.lexsort(keys)]
                tier_count = valid.sum(dtype=jnp.int32)
            else:
                tier_sorted = jnp.zeros((0, m), jnp.int32)
                tier_count = jnp.int32(0)

            res_nodes = jnp.full((max(rs, 1), m), -1, jnp.int32)
            if rs:
                pc_r = (jnp.cumsum(placed_res, dtype=jnp.int32) - placed_res)
                gid_r = jnp.where(placed_res, pc_r // m, rs)    # OOB: drop
                res_nodes = res_nodes.at[gid_r, pc_r % m].set(
                    order, mode="drop")
            all_groups = jnp.concatenate([tier_sorted, res_nodes], axis=0)

            j = jnp.arange(need, dtype=jnp.int32)
            gather = jnp.where(j < tier_count, j,
                               tier_sorted.shape[0] + j - tier_count)
            gather = jnp.clip(gather, 0, all_groups.shape[0] - 1)
            members = jnp.where(feasible, all_groups[gather], -1)
            out.append({"members": members, "feasible": feasible,
                        "n_constraints": jnp.where(feasible, best, -1)})
        return out
    return place_fat_tree


# ------------------------------------------------------------- grid runner

_GRID_CACHE: Dict[Tuple, Callable] = {}


def _grid_fn(cfg: FatTreeConfig, tp_sizes: Tuple[int, ...],
             job_gpus: Tuple[int, ...], mesh) -> Callable:
    key = (cfg, tp_sizes, job_gpus, mesh)
    fn = _GRID_CACHE.get(key)
    if fn is not None:
        return fn
    batched = jax.vmap(_snapshot_fn(cfg, tp_sizes, job_gpus))
    if mesh is not None:
        # check_vma off: each shard's body is local (no collectives), and
        # the binary search's fori_loop starts from unvarying constants
        # that the varying-axes checker would otherwise reject
        batched = jax.shard_map(batched, mesh=mesh, in_specs=P(_SNAP_AXIS),
                                out_specs=P(_SNAP_AXIS), check_vma=False)
    fn = jax.jit(batched, donate_argnums=0)
    _GRID_CACHE[key] = fn
    return fn


def fat_tree_placements(masks: np.ndarray, cfg: FatTreeConfig,
                        tp_sizes: Sequence[int], job_gpus: Sequence[int], *,
                        chunk_snapshots: int = 1024
                        ) -> List[BatchedPlacement]:
    """Device-evaluated Algorithm-5 placements, one grid per TP size.

    Returns host :class:`BatchedPlacement` objects bit-for-bit equal to
    :func:`repro.dcn.kernel.batched_fat_tree` on the same masks.
    """
    require()
    if not cfg.regular():
        raise ValueError("jax fat-tree kernel requires regular geometry")
    masks = np.asarray(masks, dtype=bool)
    snaps = masks.shape[0]
    tps = tuple(int(t) for t in tp_sizes)
    jobs = tuple(int(j) for j in job_gpus)
    outs = []
    for tp, job in zip(tps, jobs):
        m = cfg.group_nodes(tp)
        need = cfg.need_groups(tp, job)
        outs.append(BatchedPlacement(
            np.full((snaps, need, m), -1, dtype=np.int32),
            np.zeros(snaps, bool), np.full(snaps, -1, np.int64), need, m))
    if snaps == 0:
        return outs

    mesh = snapshot_mesh(_SNAP_AXIS)
    ndev = 1 if mesh is None else mesh.devices.size
    chunk = max(1, chunk_snapshots)
    chunk = -(-chunk // ndev) * ndev
    fn = _grid_fn(cfg, tps, jobs, mesh)
    sharding = None if mesh is None else NamedSharding(mesh, P(_SNAP_AXIS))

    width = cfg.num_nodes
    if masks.shape[1] != width:
        # same contract as the NumPy kernel, which rejects the mismatch in
        # its chunk-grid reshape -- the backends must not diverge on bad
        # input
        raise ValueError(
            f"fault masks have {masks.shape[1]} columns, expected "
            f"num_nodes={width}")
    for lo in range(0, snaps, chunk):
        hi = min(lo + chunk, snaps)
        rows = hi - lo
        with obs.span("dcn.jax.put", rows=rows) as sp:
            padded = -(-rows // ndev) * ndev
            block = masks[lo:hi]
            if padded != rows:
                block = np.concatenate(
                    [block, np.zeros((padded - rows, width), bool)])
            sp.set(bytes=block.nbytes)
            arg = (jnp.asarray(block) if sharding is None
                   else jax.device_put(block, sharding))
        with warnings.catch_warnings():
            warnings.filterwarnings("ignore", message=".*onat.*buffer.*")
            res = fn(arg)
        # the fetch span times the copies alone, not the wait for the
        # program
        jax.block_until_ready(res)
        with obs.span("dcn.jax.fetch", rows=rows) as sp:
            fetched = 0
            for ti, on_device in enumerate(res):
                host = {k: np.asarray(v) for k, v in on_device.items()}
                fetched += sum(v.nbytes for v in host.values())
                outs[ti].members[lo:hi] = host["members"][:rows]
                outs[ti].feasible[lo:hi] = host["feasible"][:rows]
                outs[ti].n_constraints[lo:hi] = host["n_constraints"][:rows]
            sp.set(bytes=fetched)
    return outs


__all__ = ["HAVE_JAX", "fat_tree_placements", "num_devices", "require"]
