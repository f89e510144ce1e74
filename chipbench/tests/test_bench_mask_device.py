"""The reader of ``mask_device_us_per_snapshot`` on hand-made readings
(the draw module among others, spans of other layers, a program that
draws on the host), and the module it reads against the program's own
draw."""

import importlib.util

import numpy as np
import pytest
from conftest import BENCH
from harness import window as win
from harness.runner import Reading
from repro.obs import SpanRecord

MS = 1_000_000


def _metric():
    spec = importlib.util.spec_from_file_location(
        "test_metric_mask_device_us_per_snapshot",
        BENCH / "metrics" / "mask_device_us_per_snapshot.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _span(name, start, dur, **attrs):
    return SpanRecord(name, "repro", 0, start, dur, dur, 0, attrs or None)


def _reading(spans, modules):
    specs = [win.Spec(0, 0, 10 * MS, 256), win.Spec(1, 10 * MS, 30 * MS, 256)]
    trace = None if modules is None else {"modules": modules}
    return Reading(win.Window(0, 30 * MS, specs), spans, trace, {}, {})


DRAWS = [_span("prng.device_masks", 1 * MS, MS // 10, samples=256,
               nodes=32768),
         _span("prng.device_masks", 11 * MS, MS // 10, samples=256,
               nodes=32768)]
OTHERS = [_span("sim.jax.eval_block", 1 * MS, 5 * MS, rows=256),
          _span("prng.counter_fault_masks", 20 * MS, 2 * MS, samples=512)]


def test_device_time_of_the_draw_over_the_snapshots_drawn():
    modules = {"jit_draw_counter_masks(12)": 0.004,
               "jit_draw_counter_masks(13)": 0.001,
               "jit_eval_mask(7)": 0.5}
    got = _metric().read(_reading(DRAWS + OTHERS, modules))
    # 5 ms over 512 snapshots
    assert got == pytest.approx(5e3 / 512)


@pytest.mark.parametrize("spans,modules", [
    (OTHERS, {"jit_eval_mask(7)": 0.5}),                # host draw
    (DRAWS + OTHERS, {"jit_eval_mask(7)": 0.5}),        # module not traced
    (OTHERS, {"jit_draw_counter_masks(12)": 0.004}),    # spans not kept
    (DRAWS, None)])                                     # no trace
def test_nothing_to_read(spans, modules):
    assert _metric().read(_reading(spans, modules)) is None


def test_module_is_the_programs_draw():
    import jax
    from repro.sim import jax_backend
    text = jax_backend._draw_fn(64, None).lower(
        jax.ShapeDtypeStruct((4, 2), np.uint32), np.uint32(0),
        np.bool_(False)).as_text()
    assert f"module @{_metric().MODULE} " in text
