"""Process-level JAX runtime: meshes, the engines' devices, compile cache.

* :func:`make_mesh` is the one mesh constructor of the repository.  Every
  axis is Auto, so ``with_sharding_constraint`` (``repro.parallel.shard``)
  and ``jax.shard_map`` accept the mesh alike.
* :func:`engine_devices` is the one place the simulator engines
  (``repro.sim.jax_backend``, ``repro.dcn.jax_backend``) learn which
  devices to use.  By default that is every visible device; inside
  ``with use_devices(n):`` it is the first ``n``, so a one-chip run stays on
  one chip of a four-chip host.  :func:`snapshot_mesh` builds the engines'
  1-D snapshot mesh over those devices.
* :func:`enable_compile_cache` turns on JAX's persistent compilation cache
  for entry points (``chip_smoke.py``, the benchmarks).  Importing
  ``repro`` never calls it.

JAX is imported inside the functions, so this module imports on
NumPy-only installs.
"""

from __future__ import annotations

import contextlib
import contextvars
import os
from pathlib import Path
from typing import Iterator, List, Optional, Sequence

_DEVICE_COUNT: contextvars.ContextVar[Optional[int]] = \
    contextvars.ContextVar("repro_engine_devices", default=None)

#: Cache directory under an entry point's root when
#: ``JAX_COMPILATION_CACHE_DIR`` is unset (listed in ``.gitignore``).
CACHE_DIRNAME = ".jax_cache"


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices: Optional[Sequence] = None):
    """``jax.make_mesh`` with every axis Auto."""
    import jax
    names = tuple(axis_names)
    return jax.make_mesh(tuple(axis_shapes), names,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(names),
                         devices=devices)


@contextlib.contextmanager
def use_devices(count: int) -> Iterator[None]:
    """Restrict the simulator engines to the first ``count`` devices."""
    if count < 1:
        raise ValueError(f"device count must be >= 1, got {count}")
    token = _DEVICE_COUNT.set(count)
    try:
        yield
    finally:
        _DEVICE_COUNT.reset(token)


def engine_devices() -> List:
    """Devices the simulator engines evaluate on (see :func:`use_devices`)."""
    import jax
    devs = jax.devices()
    count = _DEVICE_COUNT.get()
    if count is None:
        return devs
    if count > len(devs):
        raise RuntimeError(f"{count} devices requested, {len(devs)} visible "
                           f"({devs[0].platform})")
    return devs[:count]


def snapshot_mesh(axis: str):
    """1-D mesh over :func:`engine_devices`, or ``None`` on one device."""
    devs = engine_devices()
    if len(devs) == 1:
        return None
    return make_mesh((len(devs),), (axis,), devices=devs)


def enable_compile_cache(root: Path) -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory (JAX reads
    it itself) and no other is set.  Otherwise the cache lives at the
    fixed path ``<root>/.jax_cache``: a path that moved between runs would
    never hit.
    """
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(Path(root).resolve() / CACHE_DIRNAME)
        jax.config.update("jax_compilation_cache_dir", path)
    return path


__all__ = ["CACHE_DIRNAME", "enable_compile_cache", "engine_devices",
           "make_mesh", "snapshot_mesh", "use_devices"]
