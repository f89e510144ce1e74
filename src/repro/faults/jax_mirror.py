"""jnp mirror of the ``repro.core.prng`` threefry-2x32 stream.

The structured generators (:mod:`repro.faults.generators`) derive every
mask from uint32 threefry draws followed by pure integer/boolean
arithmetic, so a JAX backend only needs the *draws* to match bit-for-bit
-- the shared grid code then runs unchanged under ``jnp``.  This module
provides that: :func:`threefry_bits_jnp` reproduces
``repro.core.prng.threefry_bits(key, size)`` (original, non-partitionable
counter layout) on device, and :class:`JaxDraw` wires it behind the same
named-sub-stream interface as :class:`repro.faults.base.NumpyDraw`.

uint32 addition in jnp wraps modulo 2**32 by construction, so the cipher
is exact without any errstate handling.  :func:`threefry2x32_jnp` is the
repository's one jnp cipher: it takes host or traced uint32 keys, scalar
or broadcast against the counter lanes, so the sweep engine's device mask
draw (``repro.sim.jax_backend``) runs it too.  The round schedule is
``repro.core.prng``'s own.  Key derivation for :class:`JaxDraw` (seed +
fold_in) is a handful of host-side scalar hashes and reuses the NumPy
mirror directly.  Import is gated: ``HAVE_JAX`` is False on numpy-only
installs and :class:`JaxDraw` raises on construction there.
"""

from __future__ import annotations

from ..core.prng import _INJECT, _ROTATIONS, threefry_fold_in, threefry_seed

try:
    import jax.numpy as jnp
    HAVE_JAX = True
except ImportError:                                    # pragma: no cover
    jnp = None
    HAVE_JAX = False


def threefry2x32_jnp(k0, k1, c0, c1):
    """Threefry-2x32 on jnp uint32 lanes (20 rounds), bit-identical to
    :func:`repro.core.prng.threefry2x32`.

    ``k0``/``k1`` are uint32 key words: host scalars or arrays, or traced
    values, broadcast against the counter lanes ``c0``/``c1`` (a
    ``(rows, 1)`` key column hashes each row under its own key).
    """
    k0 = jnp.asarray(k0, jnp.uint32)
    k1 = jnp.asarray(k1, jnp.uint32)
    ks = (k0, k1, k0 ^ k1 ^ jnp.uint32(0x1BD11BDA))
    x0 = jnp.asarray(c0, jnp.uint32) + ks[0]
    x1 = jnp.asarray(c1, jnp.uint32) + ks[1]
    for gi, (a, b, ctr) in enumerate(_INJECT):
        for r in _ROTATIONS[gi % 2]:
            x0 = x0 + x1
            x1 = x0 ^ ((x1 << jnp.uint32(r)) | (x1 >> jnp.uint32(32 - r)))
        x0 = x0 + ks[a]
        x1 = x1 + ks[b] + jnp.uint32(ctr)
    return x0, x1


def threefry_bits_jnp(key, size: int):
    """``repro.core.prng.threefry_bits(key, size)`` (original layout) as a
    jnp uint32 vector; ``key`` is the host-side 2-word uint32 key."""
    if size == 0:
        return jnp.zeros((0,), jnp.uint32)
    odd = size % 2
    count = jnp.arange(size + odd, dtype=jnp.uint32)
    if odd:
        count = count.at[size].set(0)      # the NumPy mirror pads one zero
    half = (size + odd) // 2
    x0, x1 = threefry2x32_jnp(key[0], key[1], count[:half], count[half:])
    out = jnp.concatenate([x0, x1])
    return out[:size]


class JaxDraw:
    """Named threefry sub-streams on device: ``bits(stream, shape)`` is
    bit-identical to :class:`repro.faults.base.NumpyDraw` for the same
    seed (key chain folded host-side, lanes hashed with jnp)."""

    def __init__(self, seed: int):
        if not HAVE_JAX:
            raise RuntimeError("JaxDraw requires jax; install it or use "
                               "the NumPy masks() path")
        self._root = threefry_seed(seed)

    def bits(self, stream: int, shape):
        if isinstance(shape, int):
            shape = (shape,)
        size = 1
        for dim in shape:
            size *= int(dim)
        key = threefry_fold_in(self._root, stream)
        return threefry_bits_jnp(key, size).reshape(shape)


__all__ = ["HAVE_JAX", "jnp", "threefry2x32_jnp", "threefry_bits_jnp",
           "JaxDraw"]
