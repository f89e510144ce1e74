"""``chip_smoke.py`` rehearsed on the CPU at tiny sizes.

The script's own entry point refuses any platform but a TPU; these tests
drive its phase functions directly (``chip_smoke.run``) with tiny shapes,
on one host device in-process and on four forced host devices in a
subprocess, so a wrong path, argument or sharding rule fails here before
it costs chip time.
"""

import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SMOKE = ROOT / "chip_smoke.py"

_TINY = dict(sweep_nodes=1024, sweep_samples=64, dcn_nodes=256,
             dcn_samples=8, dcn_agg_domain=64, serve_horizon_h=7 * 24.0)


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod          # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def _cpu_env(devices: int = 1) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("XLA_FLAGS", None)
    if devices > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return env


def test_chip_smoke_phases_match_numpy_on_one_device(capsys):
    smoke = _load()
    smoke.run(smoke.Scale(**_TINY), chips=1)
    out = capsys.readouterr().out
    for phase in ("sweep", "dcn", "serve"):
        assert f"{phase}: devices=1 " in out
        assert " == numpy; " in out.split(f"{phase}: ")[-1]
    [sweep] = [ln for ln in out.splitlines()
               if ln.startswith("sweep: devices=1 ")]
    assert sweep.endswith(" masks=device")     # drawn on the device


@pytest.mark.parametrize("chips", [1, 4])
def test_chip_smoke_uses_the_chips_asked_for_on_a_four_device_host(chips):
    code = (f"import sys; sys.path.insert(0, {str(ROOT)!r})\n"
            "import chip_smoke as m\n"
            f"m.run(m.Scale(**{_TINY!r}), chips={chips})\n")
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=_cpu_env(4), timeout=600)
    assert res.returncode == 0, res.stdout + res.stderr
    assert f"sweep: devices={chips} " in res.stdout
    assert f"dcn: devices={chips} " in res.stdout
    # --chips 4 runs the sharded phases only
    assert ("serve: devices=1 " in res.stdout) == (chips == 1)


def _run_script(script: Path, cwd: Path) -> subprocess.CompletedProcess:
    return subprocess.run([sys.executable, str(script)], capture_output=True,
                          text=True, env=_cpu_env(), cwd=cwd, timeout=300)


def test_chip_smoke_refuses_the_cpu():
    res = _run_script(SMOKE, ROOT)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
    assert "needs a TPU" in res.stderr


def test_chip_smoke_fails_without_the_repository(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SMOKE, alone)
    res = _run_script(alone, tmp_path)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
