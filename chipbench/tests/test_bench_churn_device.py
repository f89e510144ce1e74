"""The reader of ``churn_mask_device_us_per_snapshot`` on hand-made
readings (the build module among others, spans of other layers, a program
that builds its interval masks on the host), and the module it reads
against the program's own build."""

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
        "test_metric_churn_mask_device_us_per_snapshot",
        BENCH / "metrics" / "churn_mask_device_us_per_snapshot.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _span(name, start, dur, **attrs):
    return SpanRecord(name, "repro", 0, start, dur, dur, 0, attrs or None)


def _reading(spans, modules):
    specs = [win.Spec(0, 0, 10 * MS, 2000), win.Spec(1, 10 * MS, 30 * MS,
                                                     2100)]
    trace = None if modules is None else {"modules": modules}
    return Reading(win.Window(0, 30 * MS, specs), spans, trace, {}, {})


BUILDS = [_span("churn.device_masks", 1 * MS, MS // 10, rows=1024,
                nodes=32768),
          _span("churn.device_masks", 12 * MS, MS // 10, rows=976,
                nodes=32768)]
OTHERS = [_span("sim.jax.eval_block", 1 * MS, 5 * MS, rows=1024),
          _span("churn.masks", 11 * MS, MS // 5, rows=976, nodes=32768),
          _span("prng.device_masks", 20 * MS, 2 * MS, samples=512)]


def test_device_time_of_the_build_over_the_intervals_built():
    modules = {"jit_build_interval_masks(3)": 0.004,
               "jit_build_interval_masks(4)": 0.002,
               "jit_draw_counter_masks(12)": 0.1,
               "jit_eval_mask(7)": 0.5}
    got = _metric().read(_reading(BUILDS + OTHERS, modules))
    # 6 ms over 2,000 intervals
    assert got == pytest.approx(6e3 / 2000)


@pytest.mark.parametrize("spans,modules", [
    (OTHERS, {"jit_eval_mask(7)": 0.5}),                   # host build
    (BUILDS + OTHERS, {"jit_eval_mask(7)": 0.5}),          # module not traced
    (OTHERS, {"jit_build_interval_masks(3)": 0.004}),      # spans not kept
    (BUILDS, None)])                                       # no trace
def test_nothing_to_read(spans, modules):
    assert _metric().read(_reading(spans, modules)) is None


def test_module_is_the_programs_build():
    import jax
    from repro.sim import jax_backend
    text = jax_backend._interval_fn(8, 64, None).lower(
        jax.ShapeDtypeStruct((8, 64), bool),
        jax.ShapeDtypeStruct((64,), np.int32), np.int32(0),
        jax.ShapeDtypeStruct((3, 16), np.int32)).as_text()
    assert f"module @{_metric().MODULE} " in text
