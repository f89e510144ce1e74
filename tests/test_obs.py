"""Telemetry subsystem: no-op guarantees, export round-trip, bit-exactness.

The load-bearing contracts:

  * disabled, every ``obs.span``/``count``/``gauge`` call is a true no-op
    -- one shared ``NULL_SPAN`` object, no allocation, and a pinned
    per-call time budget (the scale benchmark's throughput gates run in
    this state);
  * enabled, instrumentation must not change any engine's numbers: the
    sweep grids are bit-identical with telemetry on and off;
  * a collected trace survives the full export pipeline: spans/counters ->
    Chrome-trace JSON -> ``tools/trace_report.py`` parse, with the report's
    aggregates agreeing with ``Telemetry.summary()``;
  * every host layer from spec to tables has its span, and the spans land
    on the ``jax.profiler`` clock within 1 ms of their own.
"""

import json
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from repro import obs
from repro.churn import ChurnJob, ChurnSpec, control_plane_replay, \
    integrated_waste_table, monte_carlo_replay
from repro.core.control_plane import ClusterManager
from repro.obs import NULL_SPAN, Progress
from repro.sim import DcnSpec, jax_backend, run_dcn_sweep, traffic_tables
from repro.sim.engine import evaluate_mask_stream, run_sweep
from repro.sim.scenario import CounterIIDSnapshots, ScenarioSpec
from repro.sim.tables import max_job_table, waste_table

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "tools"))
import trace_report  # noqa: E402  (tools/ is not a package)

ARCHES = ("infinitehbd-k3", "nvl-72")


def _spec(samples, num_nodes=144, ratio=0.07, seed=3):
    return ScenarioSpec(num_nodes=num_nodes,
                        snapshots=CounterIIDSnapshots(ratio, samples, seed),
                        tp_sizes=(16,), architectures=ARCHES)


@pytest.fixture
def disabled():
    prev = obs.enabled()
    obs.disable()
    yield
    if prev:
        obs.enable()


# ------------------------------------------------------- disabled path


def test_disabled_span_is_shared_singleton(disabled):
    assert obs.span("a") is NULL_SPAN
    assert obs.span("b", cat="bench", anything=1) is NULL_SPAN
    with obs.span("c") as sp:
        assert sp is NULL_SPAN
        assert sp.set(latency_us=3.0) is NULL_SPAN   # set() is a no-op too


def test_disabled_calls_record_nothing(disabled):
    obs.reset()
    with obs.span("x"):
        obs.count("n", 5)
        obs.gauge("g", 1.0)
    s = obs.summary()
    assert s["spans"] == {} and s["counters"] == {} and s["gauges"] == {}


def test_disabled_overhead_pinned(disabled):
    """Per-call budget of the no-op path.  Generous (2 microseconds --
    the real cost is ~100x lower) so a noisy host cannot flake, but an
    accidental allocation/lock on the disabled path still fails."""
    n = 50_000
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(n):
            with obs.span("hot"):
                obs.count("c")
        best = min(best, time.perf_counter() - t0)
    per_call_us = best / n * 1e6
    assert per_call_us < 2.0, f"disabled span+count: {per_call_us:.3f}us/call"


# ------------------------------------------------- span nesting & summary


def test_span_nesting_self_time(tel):
    with obs.span("outer") as outer:
        time.sleep(0.002)
        with obs.span("inner"):
            time.sleep(0.005)
    recs = {r.name: r for r in tel.spans}
    assert set(recs) == {"outer", "inner"}
    assert recs["inner"].depth == 1 and recs["outer"].depth == 0
    # self time is duration minus time attributed to children, exactly
    assert recs["outer"].self_ns == \
        recs["outer"].dur_ns - recs["inner"].dur_ns
    assert recs["inner"].self_ns == recs["inner"].dur_ns
    assert recs["outer"].self_ns >= int(1e6)     # the outer 2ms sleep
    assert outer.child_ns == recs["inner"].dur_ns
    s = obs.summary()
    assert s["spans"]["outer"]["count"] == 1
    assert s["spans"]["outer"]["self_s"] < s["spans"]["outer"]["total_s"]


def test_span_attrs_and_counters(tel):
    with obs.span("work", cat="test", rows=3) as sp:
        sp.set(rate=42.5)
        obs.count("events", 2)
        obs.count("events", 3)
        obs.gauge("rss", 10.0)
        obs.gauge("rss", 12.0)
    rec = tel.spans[0]
    assert rec.attrs == {"rows": 3, "rate": 42.5} and rec.cat == "test"
    s = obs.summary()
    assert s["counters"] == {"events": 5}
    assert s["gauges"]["rss"] == {"last": 12.0, "max": 12.0, "samples": 2}


# ------------------------------------------------- export round-trip


def _collect_sample(tel):
    with obs.span("phase.a", cat="test", rows=4):
        time.sleep(0.001)
        with obs.span("phase.b", cat="test"):
            time.sleep(0.003)
        obs.count("widgets", 3)
        obs.count("widgets", 4)
        obs.gauge("rss_mb", 64.0)


def test_export_roundtrip_trace_report(tel, tmp_path):
    _collect_sample(tel)
    path = tmp_path / "t.trace.json"
    assert obs.export(str(path)) == str(path)

    trace = trace_report.load_trace(str(path))
    assert trace["displayTimeUnit"] == "ms"
    assert trace["otherData"]["summary"]["enabled"] is True

    spans = trace_report.span_summary(trace)
    ref = obs.summary()
    assert set(spans) == set(ref["spans"]) == {"phase.a", "phase.b"}
    for name in spans:
        assert spans[name]["count"] == ref["spans"][name]["count"]
        # report re-derives self-time from ts/dur nesting; must agree with
        # the collector's own child_ns accounting to ~ms rounding
        assert spans[name]["total_us"] == pytest.approx(
            ref["spans"][name]["total_s"] * 1e6, rel=0.01, abs=5.0)
        assert spans[name]["self_us"] == pytest.approx(
            ref["spans"][name]["self_s"] * 1e6, rel=0.05, abs=50.0)
    assert spans["phase.a"]["self_us"] < spans["phase.a"]["total_us"]

    totals = trace_report.counter_totals(trace)
    assert totals["widgets"] == 7

    rows = trace_report.rate_timeline(trace, "widgets", buckets=4)
    assert rows and sum(1 for _, rate in rows if rate > 0) >= 1


def test_export_json_safe_attrs(tel, tmp_path):
    with obs.span("np.attrs", n=np.int64(3), f=np.float32(1.5),
                  tup=(1, 2), none=None):
        pass
    path = tmp_path / "np.trace.json"
    obs.export(str(path))
    ev = json.loads(path.read_text())["traceEvents"][0]
    assert ev["args"]["n"] == 3 and ev["args"]["tup"] == [1, 2]
    assert ev["args"]["none"] is None and "self_us" in ev["args"]


def test_trace_report_cli(tel, tmp_path):
    _collect_sample(tel)
    path = tmp_path / "cli.trace.json"
    obs.export(str(path))
    out = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "trace_report.py"), str(path),
         "--rate", "widgets", "--buckets", "3"],
        capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
    assert "phase.a" in out.stdout and "widgets" in out.stdout


def test_trace_report_rejects_non_trace(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text('{"not": "a trace"}')
    with pytest.raises(ValueError):
        trace_report.load_trace(str(bad))


# ------------------------------------------------- engines: bit-exactness


def test_sweep_bit_exact_telemetry_on_vs_off():
    spec = _spec(41)
    prev = obs.enabled()
    try:
        obs.disable()
        off = run_sweep(spec, backend="numpy")
        obs.reset()
        obs.enable()
        on = run_sweep(spec, backend="numpy")
    finally:
        obs.reset()
        if prev:
            obs.enable()
        else:
            obs.disable()
    assert np.array_equal(off.placed_gpus, on.placed_gpus)
    assert np.array_equal(off.faulty_gpus, on.faulty_gpus)
    assert np.array_equal(off.total_gpus, on.total_gpus)


def test_sweep_emits_engine_spans(tel):
    run_sweep(_spec(33), backend="numpy")
    s = obs.summary()
    assert "sim.run_sweep" in s["spans"]
    assert "prng.counter_fault_masks" in s["spans"]
    assert s["counters"]["sim.snapshots_evaluated"] == 33
    assert s["counters"]["prng.masks_generated"] == 33
    assert s["gauges"]["prng.rss_mb"]["last"] > 0


@pytest.mark.skipif(not jax_backend.HAVE_JAX, reason="jax unavailable")
def test_jax_jit_cache_counters(tel):
    spec = _spec(17)
    run_sweep(spec, backend="jax")
    first = obs.summary()["counters"]
    assert first.get("sim.jax.jit_cache_miss", 0) >= 1
    run_sweep(spec, backend="jax")   # identical static_key -> cache hit
    second = obs.summary()["counters"]
    assert second.get("sim.jax.jit_cache_hit", 0) >= 1
    assert second["sim.jax.jit_cache_miss"] == \
        first["sim.jax.jit_cache_miss"]
    assert obs.summary()["spans"]["sim.jax.eval_block"]["count"] >= 1


# ------------------------------------------------- host layers of a spec


def _named(tel, name):
    return [r for r in tel.spans if r.name == name]


def _within(inner, outer) -> bool:
    return (outer.start_ns <= inner.start_ns
            and inner.start_ns + inner.dur_ns
            <= outer.start_ns + outer.dur_ns)


@pytest.mark.skipif(not jax_backend.HAVE_JAX, reason="jax unavailable")
def test_sweep_host_layer_spans(tel):
    # 64 nodes, two blocks of 32 snapshots drawn on the device
    result = run_sweep(_spec(64, num_nodes=64), backend="jax",
                       chunk_snapshots=32)
    waste_table(result)
    max_job_table(result)
    [models] = _named(tel, "sim.models")
    assert models.attrs == {"models": len(ARCHES)}
    blocks = _named(tel, "sim.jax.eval_block")
    assert [b.attrs["rows"] for b in blocks] == [32, 32]
    # one evaluator for the whole spec
    assert [s.attrs for s in _named(tel, "sim.jax.setup")] == [{"rows": 64}]
    puts, fetches = _named(tel, "sim.jax.put"), _named(tel, "sim.jax.fetch")
    draws = _named(tel, "prng.device_masks")
    assert len(puts) == len(draws) == len(fetches) == 2
    assert not _named(tel, "prng.counter_fault_masks")
    for block, put, draw, fetch in zip(blocks, puts, draws, fetches):
        # the copy is each row's two-word threefry key, not its mask
        assert put.attrs == {"rows": 32, "bytes": 32 * 8}
        assert draw.attrs == {"samples": 32, "nodes": 64}
        assert fetch.attrs == {"rows": 32}
        for inner in (put, draw, fetch):
            assert _within(inner, block) and inner.depth == block.depth + 1
        assert put.start_ns + put.dur_ns <= draw.start_ns
        assert draw.start_ns + draw.dur_ns <= fetch.start_ns
    for name in ("sim.tables.waste_table", "sim.tables.max_job_table"):
        [table] = _named(tel, name)
        assert table.attrs == {"rows": 64,
                               "cells": len(ARCHES) * len(result.tp_sizes)}
    # nothing left of the per-block rate instruments
    assert "sim.jax.snaps_per_sec" not in obs.summary()["gauges"]
    assert all("snaps_per_sec" not in b.attrs for b in blocks)


@pytest.mark.skipif(not jax_backend.HAVE_JAX, reason="jax unavailable")
def test_dcn_host_layer_spans(tel):
    spec = DcnSpec(num_nodes=256, fault_ratios=(0.0, 0.05), samples=4,
                   tp_sizes=(16, 32), agg_domain=64, seed=2)
    # 2 ratios x 4 snapshots = 8 rows: two blocks of 4 per TP size
    result = run_dcn_sweep(spec, backend="jax", chunk_snapshots=4)
    puts, fetches = _named(tel, "dcn.jax.put"), _named(tel, "dcn.jax.fetch")
    assert len(puts) == len(fetches) == 2 * len(spec.tp_sizes)
    assert all(p.attrs == {"rows": 4, "bytes": 4 * 256} for p in puts)
    cfg = spec.config
    # per snapshot: int32 members, a bool feasible, an int32 level
    want = [4 * (cfg.need_groups(tp, spec.job_gpus(tp))
                 * cfg.group_nodes(tp) * 4 + 1 + 4)
            for tp in spec.tp_sizes for _ in range(2)]
    assert [f.attrs for f in fetches] == [{"rows": 4, "bytes": b}
                                          for b in want]
    placements = [r for r in _named(tel, "dcn.evaluate_placements")
                  if r.attrs["variant"] == "orchestrated"]
    for inner in puts + fetches:
        assert any(_within(inner, outer) for outer in placements)
    pairs = _named(tel, "dcn.pair_counts")
    assert sorted((p.attrs["variant"], p.attrs["tp"]) for p in pairs) == \
        sorted((v, tp) for v in spec.variants for tp in spec.tp_sizes)
    assert all(p.attrs["snapshots"] == 8 for p in pairs)
    traffic_tables(result)
    assert len(_named(tel, "dcn.tables.traffic_tables")) == 1


@pytest.mark.skipif(not jax_backend.HAVE_JAX, reason="jax unavailable")
def test_spans_mirror_onto_the_profiler_clock(tel, tmp_path):
    import jax
    spec = _spec(64, num_nodes=64)
    run_sweep(spec, backend="jax", chunk_snapshots=32)   # compile first
    obs.reset()
    jax.profiler.start_trace(str(tmp_path))
    try:
        # the anchor as the chip benchmark takes it: a perf_counter_ns
        # read just inside an annotation
        with jax.profiler.TraceAnnotation("test.anchor"):
            anchor_ns = time.perf_counter_ns()
            run_sweep(spec, backend="jax", chunk_snapshots=32)
    finally:
        jax.profiler.stop_trace()
    [path] = tmp_path.glob("**/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    host = [(e.name, e.start_ns, e.duration_ns) for plane in data.planes
            if plane.name.startswith("/host:") for line in plane.lines
            for e in line.events]
    [anchor] = [start for name, start, _ in host if name == "test.anchor"]
    shift = anchor_ns - anchor
    mirrored = sorted((start + shift, dur) for name, start, dur in host
                      if name == "sim.jax.eval_block")
    recorded = sorted((r.start_ns, r.dur_ns)
                      for r in _named(tel, "sim.jax.eval_block"))
    assert len(mirrored) == len(recorded) == 2
    for (m_start, m_dur), (r_start, r_dur) in zip(mirrored, recorded):
        assert abs(m_start - r_start) < 1e6
        assert abs(m_start + m_dur - r_start - r_dur) < 1e6


@pytest.mark.skipif(not jax_backend.HAVE_JAX, reason="jax unavailable")
def test_device_programs_carry_stable_names():
    import jax
    from repro.dcn import jax_backend as dcn_jax
    models = _spec(4, num_nodes=64).models()
    sweep = jax_backend._grid_fn(models, [16], None, 64).lower(
        jax.ShapeDtypeStruct((4, 64), bool)).compile().as_text()
    assert "HloModule jit_eval_mask" in sweep
    for m in models:
        # each architecture kernel's ops sit in a scope of its name,
        # which vmap wraps: op_name="jit(eval_mask)/vmap(nvl-72)/..."
        assert f"/vmap({m.name})/" in sweep, m.name
    draw = jax_backend._draw_fn(64, None).lower(
        jax.ShapeDtypeStruct((4, 2), np.uint32), np.uint32(0),
        np.bool_(False)).compile().as_text()
    assert "HloModule jit_draw_counter_masks" in draw
    spec = DcnSpec(num_nodes=256, tp_sizes=(32,), agg_domain=64)
    dcn = dcn_jax._grid_fn(spec.config, (32,), (spec.job_gpus(32),),
                           None).lower(
        jax.ShapeDtypeStruct((4, 256), bool)).compile().as_text()
    assert "HloModule jit_place_fat_tree" in dcn


# ------------------------------------------------- progress callbacks


def test_stream_progress_custom_callback():
    spec = _spec(57)
    models = spec.models()
    masks = spec.snapshots.masks(spec.num_nodes)
    seen = []
    chunks = [masks[:16], masks[16:32], masks[32:]]
    evaluate_mask_stream(models, spec.tp_sizes, chunks, 57,
                         chunk_snapshots=16, backend="numpy",
                         progress=seen.append)
    # exact blocks of 16 rows: 16, 16, 16 and the last 9
    assert len(seen) == 4 and all(isinstance(p, Progress) for p in seen)
    assert [p.blocks_done for p in seen] == list(range(1, len(seen) + 1))
    assert [p.units_done for p in seen] == [16, 32, 48, 57]
    assert seen[-1].units_done == seen[-1].total_units == 57
    assert seen[-1].fraction == 1.0
    done = [p.units_done for p in seen]
    assert done == sorted(done)
    assert all(p.units_per_sec >= 0 for p in seen)


def test_stream_progress_default_publishes_gauges(tel):
    spec = _spec(48)
    models = spec.models()
    masks = spec.snapshots.masks(spec.num_nodes)
    evaluate_mask_stream(models, spec.tp_sizes,
                         [masks[:16], masks[16:32], masks[32:]], 48,
                         chunk_snapshots=16, backend="numpy")
    g = obs.summary()["gauges"]
    assert g["sim.stream.blocks_done"]["last"] == 3
    assert g["sim.stream.units_per_sec"]["samples"] == 3


def test_monte_carlo_streamed_progress():
    spec = ChurnSpec(trace_nodes=24, horizon_h=10 * 24.0,
                     tp_sizes=(16,), architectures=ARCHES, seed=3)
    seen = []
    streamed = monte_carlo_replay(spec, 2, engine="streamed",
                                  backend="numpy", chunk_snapshots=64,
                                  progress=seen.append)
    batched = monte_carlo_replay(spec, 2, engine="batched", backend="numpy")
    assert seen and seen[-1].units_done == seen[-1].total_units
    for a, b in zip(streamed.timelines, batched.timelines):
        assert np.array_equal(a.placed_gpus, b.placed_gpus)


# ------------------------------------------------- churn: reconfig spans


def test_churn_reconfig_spans_carry_latency_and_gpu_delta(tel):
    trace = ChurnSpec(trace_nodes=24, horizon_h=15 * 24.0, seed=5).trace(0)
    job = ChurnJob(tp_size=16, dp_size=4)
    recs = control_plane_replay(trace, job, max_events=12)
    spans = [r for r in tel.spans if r.name == "churn.reconfig"]
    assert len(spans) == len(recs)
    assert obs.summary()["counters"]["churn.reconfig_events"] == len(recs)
    prev_gpus = job.tp_size * job.dp_size
    for rec, sp in zip(recs, spans):
        assert sp.attrs["kind"] == rec.kind
        assert sp.attrs["sim_time_h"] == pytest.approx(rec.time_h, abs=1e-3)
        if rec.latency_us is None:
            assert sp.attrs["infeasible"] is True
            assert sp.attrs["gpu_delta"] == -prev_gpus
            prev_gpus = 0
        else:
            # Fig. 18's reconfiguration latency is derivable from the trace
            assert sp.attrs["latency_us"] == pytest.approx(
                rec.latency_us, abs=1e-3)
            assert sp.attrs["placed_gpus"] == rec.placed_gpus
            assert sp.attrs["gpu_delta"] == rec.placed_gpus - prev_gpus
            prev_gpus = rec.placed_gpus


# ------------------------------------------------- churn: trace, masks, tables


def test_churn_streamed_spans_and_counters(tel):
    """One ``churn.trace`` per realization (events, intervals), one
    ``churn.masks`` per block (rows, nodes) covering every interval, and
    ``churn.tables`` around the timeline slicing and each table."""
    spec = ChurnSpec(trace_nodes=24, horizon_h=10 * 24.0, tp_sizes=(16,),
                     architectures=ARCHES, seed=3)
    ens = monte_carlo_replay(spec, 2, engine="streamed", backend="numpy",
                             chunk_snapshots=16)
    intervals = [tl.num_intervals for tl in ens.timelines]
    traces = [r for r in tel.spans if r.name == "churn.trace"]
    assert [sp.attrs["realization"] for sp in traces] == [0, 1]
    assert [sp.attrs["intervals"] for sp in traces] == intervals
    events = [len(spec.trace(r).events) for r in range(2)]
    assert [sp.attrs["events"] for sp in traces] == events
    masks = [r for r in tel.spans if r.name == "churn.masks"]
    assert len(masks) == sum(-(-n // 16) for n in intervals)
    assert sum(sp.attrs["rows"] for sp in masks) == sum(intervals)
    assert all(sp.attrs["nodes"] == spec.num_nodes for sp in masks)
    counters = obs.summary()["counters"]
    assert counters["churn.fault_events"] == sum(events)
    assert counters["churn.intervals"] == sum(intervals)
    n_tables = len([r for r in tel.spans if r.name == "churn.tables"])
    integrated_waste_table(ens.timelines[0])
    ens.summary_table()
    assert len([r for r in tel.spans if r.name == "churn.tables"]) \
        == n_tables + 2 == 3


@pytest.mark.skipif(not jax_backend.HAVE_JAX, reason="jax unavailable")
def test_churn_device_masks_span_and_counter(tel):
    """On the JAX backend the streamed replay slices each block's deltas
    under ``churn.masks`` (rows, nodes) and builds its masks on the device
    under ``churn.device_masks`` (rows, nodes), inside the block's
    ``sim.jax.eval_block``; ``churn.device_masks_built`` counts the rows
    and ``sim.jax.put`` copies the deltas."""
    spec = ChurnSpec(trace_nodes=24, horizon_h=10 * 24.0, tp_sizes=(16,),
                     architectures=ARCHES, seed=3)
    ens = monte_carlo_replay(spec, 2, engine="streamed", backend="jax",
                             chunk_snapshots=16)
    total = sum(tl.num_intervals for tl in ens.timelines)
    blocks = -(-total // 16)            # one stream across realizations
    rows = [min(16, total - lo) for lo in range(0, total, 16)]
    masks = _named(tel, "churn.masks")
    built = _named(tel, "churn.device_masks")
    evals = _named(tel, "sim.jax.eval_block")
    assert [sp.attrs["rows"] for sp in masks] == rows
    assert [sp.attrs["rows"] for sp in built] == rows
    assert all(sp.attrs["nodes"] == spec.num_nodes for sp in masks + built)
    assert len(evals) == blocks
    assert all(_within(b, e) for b, e in zip(built, evals))
    assert obs.summary()["counters"]["churn.device_masks_built"] == total
    # one chunk of (row, node, step) int32 deltas per block
    from repro.core.trace import DELTA_CAPACITY
    puts = _named(tel, "sim.jax.put")
    assert [sp.attrs["bytes"] for sp in puts] == [12 * DELTA_CAPACITY] * blocks


@pytest.mark.skipif(not jax_backend.HAVE_JAX, reason="jax unavailable")
def test_interval_build_carries_its_module_name():
    import jax
    text = jax_backend._interval_fn(16, 64, None).lower(
        jax.ShapeDtypeStruct((16, 64), bool),
        jax.ShapeDtypeStruct((64,), np.int32), np.int32(0),
        jax.ShapeDtypeStruct((3, 32), np.int32)).compile().as_text()
    assert "HloModule jit_build_interval_masks" in text


# ------------------------------------------------- stragglers


def test_flag_stragglers_counter(tel):
    cm = ClusterManager(32, 4)
    times = {i: 1.0 for i in range(16)}
    times[3] = 4.0
    times[9] = 5.0
    assert cm.flag_stragglers(times, threshold=1.5) == {3, 9}
    assert obs.summary()["counters"][
        "control_plane.stragglers_flagged"] == 2
    # nothing flagged -> no counter bump
    cm.flag_stragglers({i: 1.0 for i in range(8)}, threshold=1.5)
    assert obs.summary()["counters"][
        "control_plane.stragglers_flagged"] == 2
