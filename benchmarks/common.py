"""Benchmark harness utilities: each benchmark prints CSV rows
``name,us_per_call,derived`` where ``derived`` is the paper-comparable
metric (waste ratio, MFU, cross-ToR share, ...).  Sections with CI gates
also persist a ``BENCH_<name>.json`` payload (uploaded as a workflow
artifact by the nightly job).

Telemetry: :func:`pin_runtime` enables ``repro.obs`` collection, so every
benchmark run gathers the engines' spans and counters, and
:func:`write_json` stamps the :func:`repro.obs.summary` block into every
gated payload beside the runtime provenance -- a perf regression in a
baseline comes with an attribution (which span grew, which counter moved)
instead of one opaque wall-time number.  ``REPRO_TRACE=1`` additionally
exports the full Perfetto trace at exit (``repro.obs``)."""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path
from typing import Callable, Optional

from repro import obs
from repro.runtime import enable_compile_cache

ROOT = Path(__file__).resolve().parents[1]

#: Known tcmalloc locations (the fleet-standard ``LD_PRELOAD`` for JAX CPU
#: hosts; see the CI workflow, which preloads it when the distro ships it).
TCMALLOC_PATHS = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4",
)


def pin_runtime(devices: Optional[int] = None) -> dict:
    """Pin the process runtime knobs that move benchmark timings, and
    return a description of what actually held.

    Called before JAX initializes (``benchmarks.run`` does it first thing):
    sets ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` when a
    device count is requested -- ``devices=`` argument, else the
    ``REPRO_BENCH_DEVICES`` environment variable -- and no count is pinned
    already.  ``LD_PRELOAD`` (tcmalloc) cannot be applied from inside a
    running process, so it is *reported*, not set: the CI workflow exports
    it when the library exists.  JAX's persistent compilation cache is
    turned on (``repro.runtime.enable_compile_cache``: the
    ``JAX_COMPILATION_CACHE_DIR`` directory, else ``<repo>/.jax_cache``).
    The returned dict is embedded in every gated payload (see
    :func:`write_json`) so a baseline records the runtime it was measured
    under.
    """
    if devices is None:
        env = os.environ.get("REPRO_BENCH_DEVICES", "").strip()
        devices = int(env) if env else None
    flags = os.environ.get("XLA_FLAGS", "")
    if devices and "xla_force_host_platform_device_count" not in flags:
        flags = (flags + " " if flags else "") \
            + f"--xla_force_host_platform_device_count={devices}"
        os.environ["XLA_FLAGS"] = flags
    # collect spans/counters for the payload telemetry block (and the
    # REPRO_TRACE exported trace); enabled-path overhead is block-granular
    # and the scale section's throughput gates bound it
    obs.enable()
    # a pin after jax backend init is a no-op; record it so a baseline
    # measured that way is visibly suspect (read before the cache import)
    preinitialized = "jax" in sys.modules
    cache_dir = enable_compile_cache(ROOT)
    preload = os.environ.get("LD_PRELOAD", "")
    runtime = {
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
        "tcmalloc_preloaded": "tcmalloc" in preload,
        "tcmalloc_available": next(
            (p for p in TCMALLOC_PATHS if os.path.exists(p)), None),
        "cpu_count": os.cpu_count(),
        "compile_cache": cache_dir,
        "jax_preinitialized": preinitialized,
    }
    _RUNTIME.clear()
    _RUNTIME.update(runtime)
    return runtime


_RUNTIME: dict = {}


def timed(fn: Callable, *args, name: Optional[str] = None, **kwargs):
    """Time one call; with ``name`` the call is also a ``bench.<name>``
    telemetry span (so the exported trace shows each measured region)."""
    if name is None:
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        return out, (time.perf_counter() - t0) * 1e6
    with obs.span(f"bench.{name}", cat="bench"):
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        return out, (time.perf_counter() - t0) * 1e6


def time_runs(fn: Callable, reps: int = 3,
              name: Optional[str] = None) -> float:
    """Best-of-``reps`` wall time of ``fn()``, seconds.

    The speedup gates compare best-of-N on both sides so container timing
    noise (observed ~2x swings) perturbs a ratio instead of deciding it;
    one shared implementation so the timing discipline can't diverge
    between gated sections.  With ``name``, each rep is recorded as a
    ``bench.<name>`` telemetry span (the span's own wall clock; the
    returned best-of is unchanged)."""
    best = float("inf")
    for rep in range(reps):
        with obs.span(f"bench.{name}", cat="bench", rep=rep) \
                if name else _NO_SPAN:
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
    return best


_NO_SPAN = obs.NULL_SPAN


def row(name: str, us: float, derived) -> str:
    if isinstance(derived, float):
        derived = f"{derived:.6g}"
    elif not isinstance(derived, str):
        derived = json.dumps(derived, separators=(",", ":"))
    line = f"{name},{us:.1f},{derived}"
    print(line)
    return line


def write_json(section: str, payload: dict) -> str:
    """Persist a section's machine-readable results as ``BENCH_<section>.json``
    (in ``BENCH_JSON_DIR`` when set, else the working directory)."""
    path = os.path.join(os.environ.get("BENCH_JSON_DIR", "."),
                        f"BENCH_{section}.json")
    payload = dict(payload)
    payload.setdefault("runtime", dict(_RUNTIME) if _RUNTIME
                       else pin_runtime())
    # spans/counters collected since the run started: the payload's perf
    # attribution (tools/check_bench.py validates the shape)
    payload.setdefault("telemetry", obs.summary())
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    return path
