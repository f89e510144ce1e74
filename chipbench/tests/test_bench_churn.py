"""The churn cell's check at test size: the program reads 0 off, the
control and planted faults in the interval-mask source do not, and the
churn metrics read hand-made spans and nothing without them."""

import dataclasses
import importlib.util

import numpy as np
import pytest
from conftest import BENCH, ROOT, run_small
from harness import window as win
from harness.runner import Reading
from repro.obs import SpanRecord

MS = 1_000_000


def churn_cell(**traffic):
    """``churn131k-week`` at 1,024 nodes over two days, 32-interval
    blocks: the same code paths, blocks straddling realizations."""
    from harness import cell as cellmod
    c = cellmod.load(ROOT, "churn131k-week")
    cfg = {**c.config, "trace_nodes": 512, "num_nodes": 1024}
    tr = {**c.traffic, "horizon_h": 48.0, "block": 32,
          "check_intervals": 4096, "warm_horizon_h": 24.0, **traffic}
    return dataclasses.replace(c, config=cfg, traffic=tr)


def test_churn_program_reads_zero_off():
    line = run_small(churn_cell())
    assert line["correct"] is True
    assert all(c["value"] == 0 for c in line["checks"].values())
    assert line["metrics"]["snapshots_per_s"]["value"] > 0


def test_churn_refuses_a_stream_of_many_shapes(monkeypatch):
    """A grid evaluator that cannot pad a short block to the stream's block
    shape would compile each spec's tails inside the window."""
    from harness import cell as cellmod
    from repro.sim import jax_backend

    class Unpadded:
        def __init__(self, models, tps, width, gen=None):
            pass

    monkeypatch.setattr(jax_backend, "GridEvaluator", Unpadded)
    c = churn_cell()
    with pytest.raises(cellmod.CellError, match="inside the window"):
        cellmod.driver(c.config).Driver(c.config, c.traffic)


def test_churn_control_fails():
    import control
    cell = churn_cell(check_intervals=64)
    for r in control.readings(ROOT, cell, [5, 2**35 + 3], 0.2):
        assert all(c["value"] == 0 for c in r["program"].values())
        # float32 edges and tables: both numbers fail
        assert r["control"]["grid_cells_off"]["value"] > 0
        assert r["control"]["table_values_off"]["value"] > 0


def _end_inclusive(real):
    """Masks built as if every fault also covered its end time."""
    from repro.core.trace import FaultEvent, FaultTrace

    def blocks(self, ts, block):
        later = [FaultEvent(e.node, e.start_h, np.nextafter(e.end_h, np.inf))
                 for e in self.events]
        return real(FaultTrace(self.num_nodes, self.horizon_h, later), ts,
                    block)
    return blocks


def _carry_dropped(real):
    """Every block starts from no active events: faults that began before
    the block are left out of it."""
    from repro.core.trace import FaultTrace

    def blocks(self, ts, block):
        ts = np.asarray(ts)
        for lo in range(0, len(ts), block):
            part = ts[lo:lo + block]
            inside = [e for e in self.events if e.start_h >= part[0]]
            yield from real(FaultTrace(self.num_nodes, self.horizon_h,
                                       inside), part, len(part))
    return blocks


@pytest.mark.parametrize("plant", [_end_inclusive, _carry_dropped])
def test_churn_planted_mask_fault_fails(plant, monkeypatch):
    from repro.core.trace import FaultTrace
    monkeypatch.setattr(FaultTrace, "fault_mask_blocks",
                        plant(FaultTrace.fault_mask_blocks))
    line = run_small(churn_cell())
    assert line["correct"] is False
    assert line["checks"]["grid_cells_off"]["value"] > 0


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"test_metric_{name}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _span(name, start, dur, **attrs):
    return SpanRecord(name, "repro", 0, start, dur, dur, 0, attrs or None)


def _reading(spans):
    """Two specs: 0-10 ms and 10-30 ms."""
    specs = [win.Spec(0, 0, 10 * MS, 2000), win.Spec(1, 10 * MS, 30 * MS,
                                                     2100)]
    return Reading(win.Window(0, 30 * MS, specs), spans, None, {}, {})


OTHERS = [_span("sim.jax.eval_block", 5 * MS, 3 * MS, rows=1024),
          _span("churn.monte_carlo_replay", 0, 9 * MS, realizations=2),
          _span("sim.tables.waste_table", 2 * MS, 1 * MS)]


def test_churn_mask_us_per_snapshot():
    spans = [_span("churn.masks", 1 * MS, 2 * MS, rows=1024, nodes=64),
             _span("churn.masks", 12 * MS, 1 * MS, rows=976, nodes=64)]
    got = _reader("churn_mask_us_per_snapshot")(_reading(spans + OTHERS))
    assert got == pytest.approx(3e3 / 2000)


def test_churn_trace_ms_and_table_ms_per_spec():
    spans = [_span("churn.trace", 0, 2 * MS, realization=0),
             _span("churn.trace", 3 * MS, 1 * MS, realization=1),
             _span("churn.trace", 10 * MS, 4 * MS, realization=0),
             _span("churn.tables", 8 * MS, MS // 2, realizations=2),
             _span("churn.tables", 9 * MS, MS // 2, intervals=40),
             _span("churn.tables", 29 * MS, 2 * MS, realizations=2),
             _span("churn.trace", 31 * MS, 5 * MS, realization=0)]
    reading = _reading(spans + OTHERS)
    # spec 0: 3 ms, spec 1: 4 ms (the span past the last spec is left out)
    assert _reader("churn_trace_ms")(reading) == pytest.approx(3.5)
    assert _reader("churn_table_ms")(reading) == pytest.approx(1.5)


@pytest.mark.parametrize("name", ["churn_mask_us_per_snapshot",
                                  "churn_trace_ms", "churn_table_ms"])
def test_churn_readers_return_nothing_without_their_spans(name):
    assert _reader(name)(_reading(OTHERS)) is None
