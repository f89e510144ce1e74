"""The per-layer metrics that split the host layers from inside, on
hand-made readings: spans inside and outside the window's specs, spans of
other layers, and no spans at all."""

import importlib.util

import pytest
from conftest import BENCH
from harness import window as win
from harness.runner import Reading
from repro.obs import SpanRecord

NEW = ("spec_setup_ms", "table_ms", "eval_copy_us_per_snapshot",
       "dcn_copy_us_per_snapshot", "dcn_pairs_us_per_snapshot")
MS = 1_000_000


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"test_metric_{name}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _span(name, start, dur, **attrs):
    return SpanRecord(name, "repro", 0, start, dur, dur, 0, attrs or None)


def _reading(spans):
    """Two specs of 256 snapshots: 0-10 ms and 10-30 ms."""
    specs = [win.Spec(0, 0, 10 * MS, 256), win.Spec(1, 10 * MS, 30 * MS, 256)]
    return Reading(win.Window(0, 30 * MS, specs), spans, None, {}, {})


# spans of other layers, and one of each kind past the last spec
OTHERS = [_span("sim.jax.eval_block", 5 * MS, 3 * MS, rows=256),
          _span("prng.counter_fault_masks", 1 * MS, 2 * MS, samples=256),
          _span("dcn.evaluate_placements", 2 * MS, 4 * MS)]
LATE = [_span(n, 31 * MS, 5 * MS) for n in
        ("sim.models", "sim.jax.setup", "sim.tables.waste_table")]


def test_spec_setup_ms_sums_models_and_setup_per_spec():
    spans = [_span("sim.models", 1 * MS, 2 * MS, models=13),
             _span("sim.jax.setup", 4 * MS, 1 * MS, rows=256),
             _span("sim.models", 11 * MS, 1 * MS, models=13),
             _span("sim.jax.setup", 14 * MS, 3 * MS, rows=256)]
    # spec 0: 3 ms; spec 1: 4 ms
    got = _reader("spec_setup_ms")(_reading(spans + OTHERS + LATE))
    assert got == pytest.approx(3.5)


def test_table_ms_sums_every_table_per_spec():
    spans = [_span("sim.tables.waste_table", 8 * MS, 1 * MS, rows=256),
             _span("sim.tables.max_job_table", 9 * MS, MS // 2, rows=256),
             _span("sim.tables.waste_table", 25 * MS, 2 * MS, rows=256)]
    # spec 0: 1.5 ms; spec 1: 2 ms
    got = _reader("table_ms")(_reading(spans + OTHERS + LATE))
    assert got == pytest.approx(1.75)


def test_eval_copy_is_put_and_fetch_over_their_rows():
    spans = [_span("sim.jax.put", 5 * MS, 300_000, rows=256, bytes=1),
             _span("sim.jax.fetch", 7 * MS, 100_000, rows=256),
             _span("sim.jax.put", 15 * MS, 200_000, rows=128, bytes=1),
             _span("sim.jax.fetch", 17 * MS, 40_000, rows=128)]
    got = _reader("eval_copy_us_per_snapshot")(_reading(spans + OTHERS))
    assert got == pytest.approx(640 / 384)


@pytest.mark.parametrize("name,span", [
    ("dcn_copy_us_per_snapshot", "dcn.jax.put"),
    ("dcn_copy_us_per_snapshot", "dcn.jax.fetch"),
    ("dcn_pairs_us_per_snapshot", "dcn.pair_counts")])
def test_dcn_metrics_are_per_window_snapshot(name, span):
    spans = [_span(span, 3 * MS, 1 * MS), _span(span, 20 * MS, 3 * MS)]
    got = _reader(name)(_reading(spans + OTHERS))
    assert got == pytest.approx(4000 / 512)


def test_dcn_copy_adds_put_and_fetch():
    spans = [_span("dcn.jax.put", 3 * MS, 1 * MS),
             _span("dcn.jax.fetch", 5 * MS, 3 * MS)]
    got = _reader("dcn_copy_us_per_snapshot")(_reading(spans + OTHERS))
    assert got == pytest.approx(4000 / 512)


@pytest.mark.parametrize("name", NEW)
def test_missing_spans_read_none(name):
    # a program without these spans (the parent of the change that added
    # them) reads nothing and raises nothing
    assert _reader(name)(_reading(OTHERS)) is None
    assert _reader(name)(_reading([])) is None


def test_eval_copy_needs_both_copies():
    puts = [_span("sim.jax.put", 5 * MS, 300_000, rows=256, bytes=1)]
    assert _reader("eval_copy_us_per_snapshot")(_reading(puts)) is None
