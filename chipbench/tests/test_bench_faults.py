"""A run with the timed path broken underneath comes out not correct.

Each test drives the rest of a run (set-up, window, check; the look for a
chip is skipped) at test size with one fault planted in the program:
the mask stream that returns its state unchanged, half of the snapshots
left out of a table's mean, and an answer altered where it is produced.
The cells run on one chip, so no exchange between chips can be left out.
"""

import dataclasses

import numpy as np
import pytest
from conftest import run_small, small_cell


def _stuck_stream(real):
    """A mask source whose state never advances: every call after the
    first returns the first call's masks."""
    first = {}

    def stuck(*args, **kwargs):
        out = real(*args, **kwargs)
        return first.setdefault(out.shape, out)
    return stuck


def _assert_caught(cell):
    line = run_small(cell)
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())
    return line


@pytest.mark.parametrize("name", ["fleet131k-bulk", "fleet131k-query"])
def test_sweep_mask_state_unchanged(name, monkeypatch):
    import repro.sim.engine as engine
    monkeypatch.setattr(engine, "counter_fault_masks",
                        _stuck_stream(engine.counter_fault_masks))
    line = _assert_caught(small_cell(name))
    assert line["checks"]["grid_cells_off"]["value"] > 0


@pytest.mark.parametrize("name", ["fleet131k-bulk", "fleet131k-query"])
def test_sweep_mean_over_half_the_snapshots(name, monkeypatch):
    import repro.sim.tables as sim_tables
    real = sim_tables.waste_stats
    monkeypatch.setattr(sim_tables, "waste_stats",
                        lambda series: real(series[:len(series) // 2]))
    line = _assert_caught(small_cell(name))
    assert line["checks"]["table_values_off"]["value"] > 0


@pytest.mark.parametrize("name", ["fleet131k-bulk", "fleet131k-query"])
def test_sweep_answer_altered(name, monkeypatch):
    from repro.sim.jax_backend import GridEvaluator
    real = GridEvaluator.eval_block

    def altered(self, block):
        faulty, placed = real(self, block)
        placed[1, -1, 0] += self.tps[0]      # one more group, last row
        return faulty, placed
    monkeypatch.setattr(GridEvaluator, "eval_block", altered)
    line = _assert_caught(small_cell(name))
    assert line["checks"]["grid_cells_off"]["value"] > 0


def test_dcn_mask_state_unchanged(monkeypatch):
    import repro.dcn.engine as engine
    monkeypatch.setattr(engine, "counter_fault_masks",
                        _stuck_stream(engine.counter_fault_masks))
    line = _assert_caught(small_cell("dcn2048-fig17c"))
    assert line["checks"]["grid_cells_off"]["value"] > 0


def test_dcn_mean_over_half_the_snapshots(monkeypatch):
    import repro.dcn.tables as dcn_tables
    real = dcn_tables.traffic_tables

    def half(result, **kw):
        keep = result.groups.shape[2] // 2
        cut = {k: getattr(result, k)[:, :, :keep]
               for k in ("groups", "dp_pairs", "crossing_pairs",
                         "crossing_pod_pairs", "feasible")}
        cut["n_constraints"] = result.n_constraints[:, :keep]
        return real(dataclasses.replace(result, **cut), **kw)
    monkeypatch.setattr(dcn_tables, "traffic_tables", half)
    line = _assert_caught(small_cell("dcn2048-fig17c"))
    assert line["checks"]["table_values_off"]["value"] > 0


def test_dcn_answer_altered(monkeypatch):
    import repro.dcn.jax_backend as dcn_jax
    real = dcn_jax.fat_tree_placements

    def altered(*args, **kwargs):
        out = real(*args, **kwargs)
        for bp in out:
            bp.n_constraints[:] = np.where(bp.feasible,
                                           bp.n_constraints + 1, -1)
        return out
    monkeypatch.setattr(dcn_jax, "fat_tree_placements", altered)
    line = _assert_caught(small_cell("dcn2048-fig17c"))
    assert line["checks"]["grid_cells_off"]["value"] > 0
