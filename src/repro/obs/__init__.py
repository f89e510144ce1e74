"""``repro.obs``: zero-dependency telemetry for every engine's hot path.

Spans (hierarchical timed regions), monotonic counters and gauges behind
one process-global :class:`Telemetry` handle, with Chrome-trace/Perfetto
JSON export (``tools/trace_report.py`` summarizes a trace file).  Disabled
-- the default -- every call is a true no-op (see
:mod:`repro.obs.telemetry`), so instrumentation stays in the hot paths
permanently.

Typical use (the engines already do this)::

    from repro import obs

    with obs.span("sim.evaluate_masks", backend=backend, snapshots=n):
        ...
        obs.count("sim.snapshots_evaluated", n)
        obs.gauge("prng.rss_mb", obs.rss_mb())

Enable collection with ``obs.enable()`` or ``REPRO_TRACE=1`` (atexit
export to ``REPRO_TRACE_PATH``, default ``repro.trace.json``), then
``obs.export(path)`` / ``obs.summary()``.

Enabled, every span of a process that has imported ``jax`` is mirrored as
a ``jax.profiler.TraceAnnotation`` of the same name, so a
``jax.profiler`` trace shows the program's host layers on its own clock
beside the device planes (:mod:`repro.obs.telemetry`).

Spans of the path from a spec to its tables, outermost first:

  * sweep (``repro.sim``): ``sim.models`` (building the architecture
    models), ``sim.run_sweep``, ``prng.counter_fault_masks`` (host mask
    draw), ``sim.stream.block`` / ``sim.evaluate_masks``,
    ``sim.jax.setup`` (evaluator and totals, once per ``sweep_grids``
    call or mask stream), ``sim.jax.eval_block``
    holding ``sim.jax.put`` (host to device) and ``sim.jax.fetch`` (device
    to host, int64 unpacking), then ``sim.tables.waste_table`` /
    ``sim.tables.max_job_table``;
  * DCN (``repro.dcn``): ``dcn.run_dcn_sweep``, ``dcn.evaluate_placements``
    per variant and TP (the orchestrated one holding ``dcn.jax.put`` /
    ``dcn.jax.fetch`` per block), ``dcn.pair_counts`` per variant and TP,
    then ``dcn.tables.traffic_tables``;
  * churn (``repro.churn``): ``churn.trace`` per realization (generation,
    4-GPU split and interval edges; counters ``churn.fault_events`` and
    ``churn.intervals``), ``churn.monte_carlo_replay`` holding the
    streamed evaluation with one ``churn.masks`` per block of interval
    masks, then ``churn.tables`` around the timeline slicing and each
    ``integrated_waste_table`` / ``ChurnEnsemble.summary_table``.
"""

# import the .export submodule eagerly: a first lazy import (inside
# Telemetry.export) would set the submodule as this package's ``export``
# attribute, clobbering the bound-function API below
from . import export as _export_module  # noqa: F401
from .telemetry import (NULL_SPAN, Span, SpanRecord, TELEMETRY, Telemetry,
                        configure_from_env, rss_mb)
from .progress import Progress, StreamProgress

#: Function API bound to the process-global handle -- ``obs.span(...)``
#: etc. read ``TELEMETRY.enabled`` per call, so enable/disable at any time.
span = TELEMETRY.span
count = TELEMETRY.count
gauge = TELEMETRY.gauge
summary = TELEMETRY.summary
export = TELEMETRY.export
chrome_trace = TELEMETRY.chrome_trace
reset = TELEMETRY.reset


def enable() -> Telemetry:
    return TELEMETRY.enable()


def disable() -> Telemetry:
    return TELEMETRY.disable()


def enabled() -> bool:
    return TELEMETRY.enabled


# REPRO_TRACE=1 in the environment turns collection on at first import
# (benchmarks.run, pytest, or any engine entry point alike).
configure_from_env()

__all__ = [
    "NULL_SPAN", "Progress", "Span", "SpanRecord", "StreamProgress",
    "TELEMETRY", "Telemetry", "chrome_trace", "configure_from_env", "count",
    "disable", "enable", "enabled", "export", "gauge", "reset", "rss_mb",
    "span", "summary",
]
