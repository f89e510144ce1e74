"""The control, the reference one precision step down in the program's
place, comes out not correct; the program itself reads 0."""

import pytest
from conftest import small_cell


def _readings(cell, seeds=(3, 2**31 + 5, 2**40 + 1)):
    import control
    from conftest import ROOT
    return control.readings(ROOT, cell, list(seeds), 0.2)


def test_sweep_control_fails_at_131k_gpu_counts():
    # int16 grids wrap once a count passes 32,767 GPUs: 16,384 nodes are
    # 65,536 GPUs; two architectures keep the CPU compile short
    cell = small_cell("fleet131k-bulk", num_nodes=16384)
    cell.config["architectures"] = cell.config["architectures"][:3:2]
    cell.traffic.update(snapshots=8, block=8, check_rows=64)
    for r in _readings(cell):
        assert all(c["value"] == 0 for c in r["program"].values())
        assert r["control"]["grid_cells_off"]["value"] > 0
        assert r["control"]["table_values_off"]["value"] > 0


@pytest.mark.parametrize("name", ["fleet131k-query", "dcn2048-fig17c"])
def test_control_fails_on_tables(name):
    # below int16's range only the float32 tables differ, which is enough:
    # the control has to fail one number of the cell
    for r in _readings(small_cell(name)):
        assert all(c["value"] == 0 for c in r["program"].values())
        assert r["control"]["table_values_off"]["value"] > 0
