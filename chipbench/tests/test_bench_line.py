"""The result line's shape, and the refusals: no TPU, no program."""

import json
import os
import shutil
import subprocess
import sys

import pytest
from conftest import BENCH, ROOT, run_small, small_cell


@pytest.mark.parametrize("name", ["fleet131k-bulk", "fleet131k-query",
                                  "dcn2048-fig17c"])
def test_last_line_shape(name, capsys):
    from harness import runner
    cell = small_cell(name)
    line = run_small(cell)
    runner.report(line)
    out, err = capsys.readouterr()
    last = json.loads(out.strip().splitlines()[-1])
    assert list(last)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(last)[-1] == "checks"
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 1
    assert set(last["metrics"]) == {m["name"] for m in cell.end_to_end}
    for m in cell.end_to_end:
        assert last["metrics"][m["name"]]["unit"] == m["unit"]
        assert last["metrics"][m["name"]]["value"] > 0
    assert set(last["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for k, c in last["checks"].items():
        assert c == {"value": 0, "limit": 0}
        assert f"check {k}=0 limit=0" in err
    # the compared numbers are the last lines on stderr
    assert err.strip().splitlines()[-len(last["checks"]):] == [
        f"check {k}={c['value']} limit={c['limit']}"
        for k, c in last["checks"].items()]
    assert "compiles_in_window=0" in out


def _run(cmd, cwd, **env):
    return subprocess.run(cmd, cwd=cwd, capture_output=True, text=True,
                          timeout=300, env={**os.environ, **env})


def test_refuses_the_cpu():
    r = _run([sys.executable, "chipbench/run.py", "--workload",
              "fleet131k-bulk", "--seed", "1", "--seconds", "1", "--trace",
              "0"], ROOT, JAX_PLATFORMS="cpu")
    assert r.returncode == 2
    assert r.stdout.strip() == ""
    assert "needs a TPU" in r.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("out", ".jax_cache",
                                                  "__pycache__"))
    r = _run([sys.executable, "chipbench/run.py", "--workload",
              "fleet131k-bulk", "--seed", "1", "--seconds", "1", "--trace",
              "0"], tmp_path, JAX_PLATFORMS="cpu")
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_refuses_an_unknown_cell():
    r = _run([sys.executable, "chipbench/run.py", "--workload", "nope",
              "--seed", "1", "--seconds", "1"], ROOT, JAX_PLATFORMS="cpu")
    assert r.returncode == 2 and r.stdout.strip() == ""


def test_program_must_come_from_the_checkout(tmp_path):
    from harness import cell as cellmod, runner
    runner._check_program(ROOT)
    with pytest.raises(cellmod.CellError):
        runner._check_program(tmp_path)


def test_refuses_a_chip_missing_from_the_peaks_table(monkeypatch):
    import types
    import jax
    from harness import runner
    fake = [types.SimpleNamespace(platform="tpu", device_kind=kind)
            for kind in ("TPU v9 imaginary", "TPU v5 lite")]
    monkeypatch.setattr(jax, "devices", lambda: fake[:1])
    with pytest.raises(runner.NoChip, match="peaks.json"):
        runner.devices(1)
    monkeypatch.setattr(jax, "devices", lambda: fake[1:])
    used, peaks = runner.devices(1)
    assert peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(runner.NoChip, match="needs 4 chips"):
        runner.devices(4)
