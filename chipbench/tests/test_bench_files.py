"""Every file BENCHMARK.json names is found by name, and the file keeps to
the benchmark's contract."""

import json
import re

import pytest
from conftest import ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][:2] == ["python3", "chipbench/run.py"]
    assert BENCH["paths"] == ["chipbench"]


def test_run_seconds_fit_a_full_check():
    # 2 + 14 runs per cell, each run_seconds + 60 s, 2 x 90 s of compile
    # per cell, 1200 s spare: all within 43,200 s at 24 cells
    rs = BENCH["run_seconds"]
    assert 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_files_found_by_name(w):
    from harness import cell as cellmod
    cell = cellmod.load(ROOT, w["name"])
    engine = cellmod.driver(cell.config)
    assert set(engine.LIMITS) == {"grid_cells_off", "table_values_off"}
    engine.Driver(cell.config, cell.traffic)
    for name, reader in cellmod.metric_readers(cell).items():
        assert callable(reader.read), name
    assert w["chips"] in (1, 4)
    assert len(w["why"]) <= 200


def test_names_units_and_references():
    cells = {w["name"]: w for w in BENCH["workloads"]}
    configs = {c["name"] for c in BENCH["configs"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for c in BENCH["configs"]:
        assert NAME.match(c["name"]) and c["file"].startswith("chipbench/")
        assert (ROOT / c["file"]).is_file()
        assert json.loads((ROOT / c["file"]).read_text())["reduced"] \
            == c["reduced"]
    for w in cells.values():
        assert NAME.match(w["name"]) and w["config"] in configs
    assert len({(w["config"], w["traffic"]) for w in cells.values()}) \
        == len(cells)
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(
        1, len(cells) // 2)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        moved = e2e[m["moves"]]
        # each cell of a per-layer metric reports the metric it moves
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for w in cells:
        reported = [m for m in BENCH["end_to_end"]
                    if w in m.get("workloads", cells)]
        assert "setup_s" in {m["name"] for m in reported}
        assert len(reported) >= 2
        assert any(w in m["workloads"] for m in BENCH["per_layer"])
