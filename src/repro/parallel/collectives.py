"""Ring + binary-exchange collectives over the HBD (model) axis.

The paper's design principle: the HBD only needs *neighbor* traffic, because
ring all-reduce is bandwidth-optimal [60].  These implementations make that
explicit -- every transfer is a ``ppermute`` to the adjacent rank on the ring
that the orchestrator laid over live OCSTrx links:

  * ``ring_all_reduce``    -- reduce-scatter + all-gather, 2(n-1) neighbor
                              steps, 2X(n-1)/n bytes on the wire per rank.
  * ``ring_reduce_scatter`` / ``ring_all_gather`` -- the two phases, usable
                              separately (ZeRO-1 wants RS fwd / AG on update).
  * ``binary_exchange_all_to_all`` -- Appendix G: node i talks to i XOR 2^k
                              in log2(n) rounds (the rewired ±2^k backup
                              links), O(p log p) vs the ring's O(p^2).

All functions must run inside ``shard_map`` with ``axis_name`` bound.
``impl="psum"`` falls back to the XLA-native collective so tests can assert
bit-consistency between the ring and the built-in path.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def ring_reduce_scatter(x: jnp.ndarray, axis_name: str,
                        scatter_axis: int = 0) -> jnp.ndarray:
    """Ring reduce-scatter via n-1 neighbor ppermutes.

    Input: the full array on every rank.  Output: rank i holds the fully
    reduced chunk i (along ``scatter_axis``).  Every step sends one chunk to
    the +1 neighbor -- on the orchestrated mesh this is a live OCSTrx link.
    """
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    idx = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    chunks = jnp.stack(jnp.split(x, n, axis=scatter_axis))  # (n, ...)

    def step(k, carry):
        acc = carry
        # at step k rank i forwards the partial for chunk (i - k - 1):
        # adds its own copy and hands it to the +1 neighbor, receiving the
        # partial for chunk (i - k - 2) in exchange.
        send_idx = (idx - k - 1) % n
        send = jnp.take(chunks, send_idx, axis=0) + acc
        recv = lax.ppermute(send, axis_name, perm)
        return recv

    acc = jnp.zeros_like(jnp.take(chunks, 0, axis=0))
    acc = lax.fori_loop(0, n - 1, step, acc, unroll=True)
    # after n-1 steps rank i holds chunk i reduced over all other ranks
    return acc + jnp.take(chunks, idx, axis=0)


def ring_all_gather(x: jnp.ndarray, axis_name: str,
                    gather_axis: int = 0) -> jnp.ndarray:
    """Ring all-gather via n-1 neighbor ppermutes (chunks rotate around)."""
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    idx = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    out = jnp.zeros((n,) + x.shape, x.dtype)
    out = out.at[idx].set(x)

    def step(k, carry):
        buf, cur = carry
        nxt = lax.ppermute(cur, axis_name, perm)
        src = (idx - k - 1) % n
        buf = buf.at[src].set(nxt)
        return buf, nxt

    out, _ = lax.fori_loop(0, n - 1, step, (out, x), unroll=True)
    parts = [jnp.take(out, i, axis=0) for i in range(n)]
    return jnp.concatenate(parts, axis=gather_axis)


def ring_all_reduce(x: jnp.ndarray, axis_name: str, impl: str = "ring",
                    chunk_axis: Optional[int] = None) -> jnp.ndarray:
    """All-reduce; ``impl='ring'`` uses explicit neighbor-only ppermutes
    (paper-faithful HBD traffic), ``impl='psum'`` the XLA primitive."""
    if impl == "psum":
        return lax.psum(x, axis_name)
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    axis = chunk_axis
    if axis is None:
        # pick the first dim divisible by n (pad if none)
        axis = next((i for i, d in enumerate(x.shape) if d % n == 0), None)
    if axis is None:
        flat = x.reshape(-1)
        pad = (-flat.shape[0]) % n
        padded = jnp.pad(flat, (0, pad))
        red = ring_all_gather(ring_reduce_scatter(padded, axis_name), axis_name)
        return red[: flat.shape[0]].reshape(x.shape)
    rs = ring_reduce_scatter(x, axis_name, scatter_axis=axis)
    return ring_all_gather(rs, axis_name, gather_axis=axis)


def binary_exchange_all_to_all(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Appendix-G Binary Exchange all-to-all (XOR-Bruck).

    ``x`` has leading dim n: slab d on rank i is the data destined for rank
    d.  Slabs are re-indexed by the *relative* address r = dest XOR rank,
    which is invariant while a slab travels: in round k every rank exchanges
    with partner i XOR 2^k exactly the slabs whose r has bit k set (half the
    buffer, so n/2 slabs x log2(n) rounds = O(p log p) total traffic, vs the
    ring's O(p^2)).  A slab with relative address r is forwarded on every
    set bit of r and therefore ends on rank src XOR r == dest.  Each partner
    is a ±2^k neighbor -- exactly the rewired backup links of §7/Appendix G.

    Output layout matches ``all_to_all_baseline``: slab j = data from rank j.
    """
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    if n & (n - 1):
        raise ValueError("binary exchange needs a power-of-two axis")
    idx = lax.axis_index(axis_name)
    log2n = n.bit_length() - 1
    rel = jnp.arange(n)
    # re-index slabs by relative address: buf[r] = slab destined to (i XOR r)
    buf = jnp.take(x, rel ^ idx, axis=0)

    for k in range(log2n):
        bit = 1 << k
        partner_perm = [(i, i ^ bit) for i in range(n)]
        mask = (((rel >> k) & 1) == 1).reshape((n,) + (1,) * (buf.ndim - 1))
        send = jnp.where(mask, buf, jnp.zeros_like(buf))
        recv = lax.ppermute(send, axis_name, partner_perm)
        buf = jnp.where(mask, recv, buf)
    # buf[r] now holds the slab from rank (i XOR r) destined to us;
    # relabel to source-major order
    return jnp.take(buf, rel ^ idx, axis=0)


def all_to_all_baseline(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """XLA-native all-to-all over the leading slab dim (comparison point)."""
    return lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0,
                          tiled=False)
