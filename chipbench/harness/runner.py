"""One run of one cell: set-up, the measured window, the check, the line.

Set-up loads the program, turns on JAX's persistent compilation cache in
``chipbench/.jax_cache`` (a fixed path inside the checkout), and drives
the cell's own shapes through the timed path until they are compiled.
The window is a closed loop of specs (:mod:`harness.window`).  With
``trace`` the window runs under the JAX profiler and ``repro.obs``, and
the line carries the per-layer metrics; without it, the end-to-end ones.
After the window the check compares what the timed path produced with the
plain reference (the cell's driver), and every compared number is printed
beside its limit.
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Dict, Optional, Tuple

import numpy as np

from . import cell as cellmod
from . import window as win

HERE = Path(__file__).resolve().parents[1]      # chipbench/
OUT = HERE / "out"
CACHE = HERE / ".jax_cache"
PEAKS = HERE / "peaks.json"


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer than the cell asks for."""


@dataclasses.dataclass
class Reading:
    """What a per-layer metric reader may read."""

    window: win.Window
    spans: list                 # repro.obs SpanRecords started in the window
    trace: Optional[dict]       # harness.xplane.reduce() of the window
    peaks: dict                 # the device's row of peaks.json
    config: dict


def prepare_env(root: Path) -> None:
    """Before JAX is imported: the cache and logs go inside the checkout,
    and the program under test is the checkout's own ``src``."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    os.environ.setdefault("TPU_LOG_DIR", str(OUT / "tpu_logs"))
    os.environ.pop("REPRO_TRACE", None)
    sys.path.insert(0, str(Path(root) / "src"))


def devices(chips: int):
    """The first ``chips`` TPUs and their row of the peaks table."""
    import jax
    found = jax.devices()
    if found[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {found[0].platform!r}")
    if len(found) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(found)}")
    table = json.loads(PEAKS.read_text())["devices"]
    kind = found[0].device_kind
    if kind not in table:
        raise NoChip(f"device kind {kind!r} is not in {PEAKS.name}")
    return found[:chips], table[kind]


def spec_seed(seed: int, stream: int, index: int) -> int:
    """63-bit seed of one spec: ``stream`` 0 is the window, 1 the warm-up."""
    words = np.random.SeedSequence([seed % (1 << 64), stream, index]) \
        .generate_state(2, np.uint32)
    return (int(words[0]) << 31) | (int(words[1]) >> 1)


def _check_program(root: Path) -> None:
    import repro
    where = Path(repro.__file__).resolve()
    if Path(root).resolve() / "src" not in where.parents:
        raise cellmod.CellError(f"repro imported from {where}, not from this "
                                f"checkout's src/")


def _memory_peak(used) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in used]
    return None if any(p is None for p in peaks) else int(max(peaks))


class Session:
    """The program loaded and the cell's driver built, for one or more
    windows (the benchmark makes one; the control tool one per seed)."""

    def __init__(self, root: Path, cell: cellmod.Cell):
        import jax
        from repro.runtime import enable_compile_cache
        _check_program(root)
        enable_compile_cache(root)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        self.engine = cellmod.driver(cell.config)
        self.driver = self.engine.Driver(cell.config, cell.traffic)

    def submit(self, seed: int, stream: int, index: int) -> win.Spec:
        spec = self.driver.spec(spec_seed(seed, stream, index), index)
        start = time.perf_counter_ns()
        output = self.driver.run(spec)
        return win.Spec(index, start, time.perf_counter_ns(),
                        self.driver.snapshots(spec), spec, output)

    def warm(self, seed: int) -> None:
        """Drive the cell's shapes through the timed path once."""
        self.driver.run(self.driver.warm_spec(spec_seed(seed, 1, 0)))

    def window(self, seed: int, seconds: float) -> Tuple[win.Window, int]:
        """The measured window, and the anchor of its trace annotation."""
        import jax
        with jax.profiler.TraceAnnotation("chipbench.window"):
            anchor_ns = time.perf_counter_ns()
            window = win.closed_loop(lambda i: self.submit(seed, 0, i),
                                     seconds)
        return window, anchor_ns

    def check(self, window: win.Window, seed: int,
              control: bool = False) -> Dict[str, dict]:
        """Every compared number of the window beside its limit."""
        rng = np.random.default_rng([seed % (1 << 64), 2])
        values = self.driver.check(window.specs, rng, control=control)
        return {k: {"value": v, "limit": self.engine.LIMITS[k]}
                for k, v in values.items()}


def measure(root: Path, cell: cellmod.Cell, seed: int, seconds: float,
            trace: bool, t0_ns: int, used, peaks: dict) -> dict:
    """Set-up, window and check of one run; returns the result line."""
    import jax
    from repro import obs
    from repro.runtime import use_devices
    session = Session(root, cell)
    readers = cellmod.metric_readers(cell) if trace else {}
    counter = win.CompileCounter()
    trace_dir = OUT / "trace" / cell.name
    with use_devices(cell.chips):
        session.warm(seed)
        setup_s = (time.perf_counter_ns() - t0_ns) / 1e9
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.enable_hlo_proto = False
            obs.reset()
            obs.enable()
            jax.profiler.start_trace(str(trace_dir),
                                     profiler_options=options)
        counter.active = True
        window, anchor_ns = session.window(seed, seconds)
        counter.active = False
        if trace:
            jax.profiler.stop_trace()
            obs.disable()
        memory_peak = _memory_peak(used)

    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(used), "memory_peak_bytes": memory_peak}
    line: Dict[str, object] = {}
    if trace:
        from . import xplane
        spans = [s for s in obs.TELEMETRY.spans
                 if window.start_ns <= s.start_ns < window.end_ns]
        named = [(s.name, s.start_ns, s.start_ns + s.dur_ns) for s in spans]
        reduced = xplane.reduce(xplane.extract(str(trace_dir)), anchor_ns,
                                (window.start_ns, window.end_ns), cell.chips,
                                named)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        reading = Reading(window, spans, reduced, peaks, cell.config)
        metrics = {}
        for m in cell.per_layer:
            value = readers[m["name"]].read(reading)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    else:
        e2e = {"snapshots_per_s": win.snapshots_per_s(window),
               "spec_p95_ms": win.spec_p95_ms(window), "setup_s": setup_s}
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    print(f"setup_s={setup_s} window_s={window.seconds} "
          f"specs={len(window.specs)} snapshots={window.snapshots} "
          f"compiles_in_window={counter.compiles} "
          f"jaxpr_traces_in_window={counter.traces}", flush=True)
    checks = session.check(window, seed)
    head = {"correct": all(c["value"] <= c["limit"] for c in checks.values()),
            "attempted": len(window.specs), "failed": 0, "metrics": metrics,
            "device": device}
    head.update(line)
    head["checks"] = checks
    return head


def report(line: dict) -> None:
    """Compared numbers last on stderr, then the result as stdout's last
    line."""
    for name, c in line["checks"].items():
        print(f"check {name}={c['value']} limit={c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)


def run(root: Path, name: str, seed: int, seconds: float, trace: bool,
        t0_ns: int) -> int:
    try:
        cell = cellmod.load(root, name)
        prepare_env(root)
        used, peaks = devices(cell.chips)
        line = measure(root, cell, seed, seconds, trace, t0_ns, used, peaks)
    except (cellmod.CellError, NoChip, ImportError) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    report(line)
    return 0
