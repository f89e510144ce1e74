"""The four-chip bulk cell at test size, on four virtual CPU devices: the
program reads 0 off, and the exchange between chips left out (one chip's
rows of a block dropped) does not.

The devices are forced before JAX starts, so the cell runs in a child
process (this file as a script); the test reads the child's lines.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent


def _one_chip_dropped(real):
    """The rows the last chip evaluated never come back: its share of the
    block's grid stays at zero."""
    def eval_block(self, block):
        faulty, placed = real(self, block)
        share = -(-block.shape[0] // self.ndev)
        faulty[:, (self.ndev - 1) * share:] = 0
        placed[:, (self.ndev - 1) * share:] = 0
        return faulty, placed
    return eval_block


def main() -> None:
    sys.path.insert(0, str(HERE))
    import jax
    from conftest import run_small, small_cell
    from repro.sim.jax_backend import GridEvaluator
    assert len(jax.devices()) == 4, jax.devices()
    # two architectures keep the CPU compile short
    cell = small_cell("fleet131k-bulk-x4")
    cell.config["architectures"] = cell.config["architectures"][:3:2]
    line = run_small(cell)
    print(json.dumps({"case": "program", "correct": line["correct"],
                      "checks": line["checks"],
                      "devices": line["device"]["count"]}), flush=True)
    GridEvaluator.eval_block = _one_chip_dropped(GridEvaluator.eval_block)
    line = run_small(cell)
    print(json.dumps({"case": "one_chip_dropped", "correct": line["correct"],
                      "checks": line["checks"],
                      "devices": line["device"]["count"]}), flush=True)


def test_four_chip_bulk_exchange_left_out_fails():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, __file__], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    lines = {d["case"]: d for d in map(json.loads, (
        ln for ln in proc.stdout.splitlines() if ln.startswith("{")))}
    assert lines["program"]["devices"] == 4
    assert lines["program"]["correct"] is True
    assert all(c["value"] == 0 for c in lines["program"]["checks"].values())
    assert lines["one_chip_dropped"]["correct"] is False
    assert lines["one_chip_dropped"]["checks"]["grid_cells_off"]["value"] > 0


if __name__ == "__main__":
    main()
