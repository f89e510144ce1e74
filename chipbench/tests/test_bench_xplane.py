"""The reduction from a profiler trace to busy time, op totals and gaps."""

import gzip
import json

import pytest
from conftest import BENCH
from harness import xplane

FIXTURE = BENCH / "tests" / "fixtures"


def test_busy_idle_and_gap_names_on_a_hand_made_trace():
    trace = {
        "devices": {"0": {
            "ops": [["fusion.1", 1000, 500], ["fusion.2", 1400, 300],
                    ["copy.3", 3000, 1000]],
            "modules": [["jit_eval_mask(1)", 1000, 700],
                        ["jit_eval_mask(1)", 3000, 1000]]}},
        "host": [["chipbench.window", 500, 10000],
                 ["chipbench.engine", 600, 3000],
                 ["chipbench.table", 4500, 2000]]}
    # perf_counter read 1,000,500 ns at the anchor: trace + 1,000,000
    spans = [("prng.counter_fault_masks", 1_001_200, 1_002_600)]
    out = xplane.reduce(trace, 1_000_500, (1_000_500, 1_010_500), 1, spans)
    assert out["window_s"] == pytest.approx(10e-6)
    assert out["busy_s"] == pytest.approx(1.7e-6)     # union, not the sum
    assert out["modules"] == {"jit_eval_mask(1)": pytest.approx(1.7e-6)}
    assert out["device_ops"][0] == ["copy.3", pytest.approx(1e-6)]
    gaps = dict(out["idle_gaps"])
    # 500-1000 lies in the engine call; 1700-3000 in the mask draw inside
    # it (innermost); 4000-10500 mostly in the table (midpoint 7250 is past
    # it, so outside any span)
    assert gaps == {"chipbench.engine": pytest.approx(0.5e-6),
                    "prng.counter_fault_masks": pytest.approx(1.3e-6),
                    "outside any span": pytest.approx(6.5e-6)}
    assert sum(gaps.values()) + out["busy_s"] == pytest.approx(10e-6)


def test_chips_are_averaged_and_clipped_to_the_window():
    ops = [["op", 0, 4000], ["op", 6000, 8000]]
    trace = {"devices": {"0": {"ops": ops, "modules": []},
                         "1": {"ops": ops[:1], "modules": []},
                         "2": {"ops": ops, "modules": []}},
             "host": [["chipbench.window", 1000, 9000]]}
    out = xplane.reduce(trace, 1000, (1000, 11000), 2)
    # chip 0: 3000 (clipped) + 5000; chip 1: 3000; chip 2 is not the cell's
    assert out["busy_s"] == pytest.approx((8000 + 3000) / 2 / 1e9)
    with pytest.raises(ValueError):
        xplane.reduce(trace, 1000, (1000, 11000), 4)


def _covered_ns(events, lo, hi):
    """Time covered by at least one event, by a sweep over the edges."""
    edges = sorted([(max(a, lo), 1) for _, a, d in events if a + d > lo
                    and a < hi] + [(min(a + d, hi), -1) for _, a, d in events
                                   if a + d > lo and a < hi])
    depth, last, total = 0, None, 0.0
    for t, step in edges:
        if depth > 0:
            total += t - last
        depth += step
        last = t
    return total


@pytest.mark.parametrize("path", sorted(FIXTURE.glob("*.json.gz")),
                         ids=lambda p: p.name)
def test_recorded_trace(path):
    with gzip.open(path, "rt") as f:
        rec = json.load(f)
    lo, hi = rec["window"]
    dev = rec["trace"]["devices"]["0"]
    out = xplane.reduce(rec["trace"], rec["anchor_ns"], (lo, hi),
                        rec["chips"])
    assert out["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert out["busy_s"] == pytest.approx(_covered_ns(dev["ops"], lo, hi)
                                          / 1e9)
    assert 0 < out["busy_s"] < out["window_s"]
    module_s = sum(min(a + d, hi) - max(a, lo) for _, a, d in dev["modules"]
                   if a + d > lo and a < hi) / 1e9
    assert sum(out["modules"].values()) == pytest.approx(module_s)
    assert all(k.startswith("jit_eval_mask") for k in out["modules"])
    gaps = dict(out["idle_gaps"])
    assert set(gaps) <= {"chipbench.engine", "chipbench.table",
                         "outside any span"}
    assert sum(gaps.values()) == pytest.approx(out["window_s"]
                                               - out["busy_s"])
    assert all(" = " in name for name, _ in out["device_ops"])
