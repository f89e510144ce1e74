"""Canonical counter-threefry fault-mask stream, kept with the benchmark.

Snapshot ``i`` of a stream with seed ``s`` is the node mask
``bits < round(ratio * 2**32)``, where ``bits`` are the uint32 words of
``jax.random.bits(fold_in(PRNGKey(s), i), (nodes,))`` in JAX's original
(non-partitionable) threefry-2x32 layout.  This is the stream the
simulator's ``CounterIIDSnapshots`` and ``DcnSpec`` promise; the benchmark
regenerates the rows it checks from here, never from the program, so a
later change to how the program draws masks is held to the same stream.
"""

from __future__ import annotations

import numpy as np

_U32 = np.uint32
_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))
# key-schedule words injected after each group of four rounds, and the
# round-group counter added to the second word
_INJECT = ((1, 2, 1), (2, 0, 2), (0, 1, 3), (1, 2, 4), (2, 0, 5))


def threefry2x32(k0, k1, x0, x1):
    """Threefry-2x32 (20 rounds) on broadcast uint32 arrays."""
    k0, k1 = np.asarray(k0, _U32), np.asarray(k1, _U32)
    ks = (k0, k1, k0 ^ k1 ^ _U32(0x1BD11BDA))
    x0 = np.asarray(x0, _U32) + ks[0]
    x1 = np.asarray(x1, _U32) + ks[1]
    for group, (a, b, ctr) in enumerate(_INJECT):
        for r in _ROTATIONS[group % 2]:
            x0 = x0 + x1
            x1 = x0 ^ ((x1 << _U32(r)) | (x1 >> _U32(32 - r)))
        x0 = x0 + ks[a]
        x1 = x1 + ks[b] + _U32(ctr)
    return x0, x1


def seed_key(seed: int) -> np.ndarray:
    """Raw words of ``jax.random.PRNGKey(seed)`` (64-bit seed)."""
    s = int(seed) & 0xFFFFFFFFFFFFFFFF
    return np.array([s >> 32, s & 0xFFFFFFFF], dtype=_U32)


def fold_in(key: np.ndarray, data: np.ndarray) -> np.ndarray:
    """``jax.random.fold_in(key, d)`` for every ``d``: ``(len(data), 2)``."""
    data = np.asarray(data, dtype=np.int64)
    x0, x1 = threefry2x32(key[0], key[1],
                          ((data >> 32) & 0xFFFFFFFF).astype(_U32),
                          (data & 0xFFFFFFFF).astype(_U32))
    return np.stack([x0, x1], axis=-1)


def threshold(ratio: float) -> int:
    """Integer threshold of a Bernoulli(ratio) draw on uint32 words."""
    return min(1 << 32, max(0, int(round(float(ratio) * (1 << 32)))))


def fault_masks(num_nodes: int, ratio: float, seed: int,
                rows: np.ndarray) -> np.ndarray:
    """``(len(rows), num_nodes)`` bool masks of the given snapshot indices."""
    rows = np.asarray(rows, dtype=np.int64)
    thresh = threshold(ratio)
    if thresh >= (1 << 32):
        return np.ones((rows.size, num_nodes), dtype=bool)
    keys = fold_in(seed_key(seed), rows)
    # the original layout hashes the flat counter 0..n-1 (padded to even)
    # split in two halves: word j of the first half pairs with word j of
    # the second, and the outputs are concatenated back in that order
    half = (num_nodes + 1) // 2
    counter = np.arange(2 * half, dtype=_U32)
    counter[num_nodes:] = 0
    x0, x1 = threefry2x32(keys[:, :1], keys[:, 1:],
                          counter[None, :half], counter[None, half:])
    bits = np.concatenate([x0, x1], axis=1)[:, :num_nodes]
    return bits < _U32(thresh)
