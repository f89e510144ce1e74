#!/usr/bin/env python3
"""Bring-up check: the simulator's three device programs on a TPU.

Drives each program once through its public engine with ``backend="jax"``
and compares every device grid with the NumPy engine, bit for bit:

1. sweep -- ``run_sweep`` at 131,072 GPUs (32,768 nodes, paper Table 2's
   top row): all registered architectures x 4,096 counter-threefry
   snapshots at the Appendix-A mean fault ratio x TP (16, 32, 64);
2. dcn -- ``run_dcn_sweep`` on the README's Fig. 17c grid (2,048 nodes,
   fault ratios 0 / 3% / 7%, 256 snapshots each, TP-32);
3. serve -- ``run_serve_sweep`` on the README's serving example (200
   nodes, 60 days, TP-16, Poisson + diurnal streams).

Usage::

    python3 chip_smoke.py            # one chip, all three phases
    python3 chip_smoke.py --chips 4  # sweep + dcn, snapshot axis on 4 chips

Informational lines come first; on success the last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any mismatch or error, or a first device that is not a TPU, exits
non-zero without that line.  JAX's persistent compilation cache goes to
``JAX_COMPILATION_CACHE_DIR`` when set, else to ``<repo>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path
from typing import Callable, Sequence, Tuple

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))


@dataclasses.dataclass(frozen=True)
class Scale:
    """Sizes of the three phases (the defaults are the chip run's)."""

    sweep_nodes: int = 32_768
    sweep_samples: int = 4096
    sweep_tps: Tuple[int, ...] = (16, 32, 64)
    fault_ratio: float = 0.0233          # Appendix-A mean node-fault ratio
    dcn_nodes: int = 2048
    dcn_samples: int = 256
    dcn_agg_domain: int = 512
    serve_nodes: int = 200
    serve_horizon_h: float = 60 * 24.0


def _timed(fn: Callable):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _same(phase: str, ref, got, fields: Sequence[str]) -> None:
    if got.backend != "jax":
        raise AssertionError(f"{phase}: ran on backend {got.backend!r}")
    for field in fields:
        a, b = getattr(ref, field), getattr(got, field)
        if a.shape != b.shape or not np.array_equal(a, b):
            diff = (int(np.count_nonzero(a != b)) if a.shape == b.shape
                    else f"shape {a.shape} vs {b.shape}")
            raise AssertionError(f"{phase}: {field} differs from numpy "
                                 f"({diff} cells)")


def _peak_bytes() -> str:
    from repro.runtime import engine_devices
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in engine_devices()]
    if any(p is None for p in peaks):
        return "n/a"
    return str(max(peaks))


def _report(phase: str, setup_s: float, steady_s: float, items: int,
            unit: str, **extra) -> None:
    from repro.runtime import engine_devices
    fields = {"devices": len(engine_devices()),
              "setup_s(incl. compile)": f"{setup_s:.3f}",
              "steady_s": f"{steady_s:.3f}",
              f"{unit}_per_s": f"{items / steady_s:.1f}",
              "peak_bytes_in_use": _peak_bytes(), **extra}
    print(f"{phase}: " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def sweep_phase(scale: Scale) -> None:
    from repro.sim import (CounterIIDSnapshots, MODEL_REGISTRY, ScenarioSpec,
                           run_sweep)
    spec = ScenarioSpec(
        num_nodes=scale.sweep_nodes,
        snapshots=CounterIIDSnapshots(fault_ratio=scale.fault_ratio,
                                      samples=scale.sweep_samples, seed=0),
        tp_sizes=scale.sweep_tps, architectures=tuple(MODEL_REGISTRY))
    first, setup_s = _timed(lambda: run_sweep(spec, backend="jax"))
    dev, steady_s = _timed(lambda: run_sweep(spec, backend="jax"))
    ref = run_sweep(spec, backend="numpy")
    fields = ("total_gpus", "faulty_gpus", "placed_gpus")
    _same("sweep (first call)", ref, first, fields)
    _same("sweep", ref, dev, fields)
    if dev.names != ref.names:
        raise AssertionError(f"sweep: architectures {dev.names} vs {ref.names}")
    waste = dev.waste_ratio
    sanity = " ".join(
        f"mean_waste_tp32[{name}]={waste[dev.index(name), :, dev.tp_index(32)].mean():.6f}"
        for name in ("infinitehbd-k3", "nvl-72"))
    _report("sweep", setup_s, steady_s, scale.sweep_samples, "snapshots",
            masks="device")
    print(f"sweep: {len(dev.names)} architectures x {dev.num_snapshots} "
          f"snapshots x TP {tuple(int(t) for t in dev.tp_sizes)} at "
          f"{spec.num_nodes} nodes == numpy; {sanity}", flush=True)


def dcn_phase(scale: Scale) -> None:
    from repro.dcn.tables import cross_tor_curve
    from repro.sim import DcnSpec, run_dcn_sweep
    spec = DcnSpec(num_nodes=scale.dcn_nodes, fault_ratios=(0.0, 0.03, 0.07),
                   samples=scale.dcn_samples, tp_sizes=(32,), job_scale=0.85,
                   agg_domain=scale.dcn_agg_domain)
    first, setup_s = _timed(lambda: run_dcn_sweep(spec, backend="jax"))
    dev, steady_s = _timed(lambda: run_dcn_sweep(spec, backend="jax"))
    ref = run_dcn_sweep(spec, backend="numpy")
    fields = ("groups", "dp_pairs", "crossing_pairs", "crossing_pod_pairs",
              "feasible", "n_constraints")
    _same("dcn (first call)", ref, first, fields)
    _same("dcn", ref, dev, fields)
    snaps = len(spec.fault_ratios) * spec.samples
    _report("dcn", setup_s, steady_s, snaps, "snapshots", masks="host")
    curve = cross_tor_curve(dev)
    print(f"dcn: {len(spec.variants)} variants x {snaps} snapshots at "
          f"{spec.num_nodes} nodes == numpy; orchestrated cross_tor_share at "
          f"7% faults={curve[0.07]}", flush=True)


def serve_phase(scale: Scale) -> None:
    from repro.churn import ChurnJob, ChurnSpec, replay_trace
    from repro.slo import (DiurnalArrivals, PoissonArrivals, ServeSpec,
                           run_serve_sweep, slo_table)
    cspec = ChurnSpec(trace_nodes=scale.serve_nodes,
                      horizon_h=scale.serve_horizon_h, tp_sizes=(16,))
    trace = cspec.trace(0)
    timeline = replay_trace(trace, tp_sizes=cspec.tp_sizes,
                            architectures=cspec.architectures,
                            job=ChurnJob(tp_size=16), backend="jax")
    # the control-plane replay (reconfiguration stalls) is host code that
    # no backend touches: replay it once and give numpy the same log
    timeline_ref = dataclasses.replace(
        replay_trace(trace, tp_sizes=cspec.tp_sizes,
                     architectures=cspec.architectures, backend="numpy"),
        reconfigs=timeline.reconfigs)
    _same("serve timeline", timeline_ref, timeline,
          ("edges_h", "total_gpus", "faulty_gpus", "placed_gpus"))

    def serve_spec(tl) -> ServeSpec:
        return ServeSpec(timeline=tl,
                         arrivals=(PoissonArrivals(80.0, seed=1),
                                   DiurnalArrivals(60.0, seed=2,
                                                   amplitude=0.5)),
                         req_per_gpu_hour=0.05, slo_h=2.0, patience_h=12.0)

    spec_dev, spec_ref = serve_spec(timeline), serve_spec(timeline_ref)
    first, setup_s = _timed(lambda: run_serve_sweep(spec_dev, backend="jax"))
    dev, steady_s = _timed(lambda: run_serve_sweep(spec_dev, backend="jax"))
    ref = run_serve_sweep(spec_ref, backend="numpy")
    fields = ("served", "served_cum", "gone_cum", "queue_depth")
    _same("serve (first call)", ref, first, fields)
    _same("serve", ref, dev, fields)
    intervals = dev.edges_h.size
    _report("serve", setup_s, steady_s, intervals, "intervals")
    slo = {(r["arrival"], r["architecture"]): r["slo_attainment"]
           for r in slo_table(dev)}
    ihbd = [v for (_, a), v in slo.items() if a.startswith("infinitehbd")]
    print(f"serve: {len(dev.arrival_labels)} streams x {len(dev.names)} "
          f"architectures x {intervals} intervals == numpy; "
          f"min infinitehbd slo_attainment={min(ihbd):.6f}", flush=True)


def run(scale: Scale, chips: int = 1) -> None:
    """All phases of one run on ``chips`` devices (four: the sharded
    sweep and dcn phases only)."""
    from repro.runtime import use_devices
    from repro.sim import jax_backend
    with use_devices(chips):
        if jax_backend.num_devices() != chips:
            raise RuntimeError(f"engines see {jax_backend.num_devices()} "
                               f"devices, wanted {chips}")
        sweep_phase(scale)
        dcn_phase(scale)
        if chips == 1:
            serve_phase(scale)


def _cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the snapshot-sharded phases, on 4 chips")
    args = ap.parse_args(argv)

    from repro.runtime import enable_compile_cache
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} visible",
              file=sys.stderr)
        return 1
    cache = enable_compile_cache(ROOT)
    before = _cache_entries(cache)
    print(f"jax {jax.__version__}, {len(devices)} x {devices[0].device_kind}"
          f" visible, running on {args.chips}; compile cache {cache} "
          f"({before} entries)", flush=True)
    t0 = time.perf_counter()
    run(Scale(), chips=args.chips)
    print(f"total_s={time.perf_counter() - t0:.3f}; compile cache "
          f"{_cache_entries(cache) - before} new entries", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
