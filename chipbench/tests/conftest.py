"""Tests of the benchmark itself, on the CPU at small sizes.

Run from the root of the repository::

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests
"""

import dataclasses
import os
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# CPU programs compiled by the tests stay apart from the chip's cache
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      str(BENCH / "out" / "test_jax_cache"))
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

#: Test sizes of each configuration and mix: the same code paths as the
#: cells, small enough for the CPU.
SMALL_CONFIG = {
    "fleet131k": {"num_nodes": 1024},
    "dcn2048": {"num_nodes": 512, "agg_domain": 128},
}
SMALL_TRAFFIC = {
    "bulk": {"snapshots": 64, "block": 32, "check_rows": 4096},
    "query": {"snapshots": 32, "block": 32, "check_rows": 4096},
    "fig17c": {"samples": 8, "check_rows": 4096},
}


def small_cell(name: str, **config):
    """The cell ``name`` of BENCHMARK.json at test size."""
    from harness import cell as cellmod
    c = cellmod.load(ROOT, name)
    cfg = {**c.config, **SMALL_CONFIG[c.config_name], **config}
    traffic = {**c.traffic, **SMALL_TRAFFIC[c.traffic_name.split("-")[0]]}
    return dataclasses.replace(c, config=cfg, traffic=traffic)


def run_small(cell, seed: int = 2**33 + 7, seconds: float = 0.3):
    """The rest of a run after the look for a chip: set-up, window, check."""
    import jax
    import time
    from harness import runner
    return runner.measure(ROOT, cell, seed, seconds, False,
                          time.perf_counter_ns(), jax.devices()[:cell.chips],
                          {"hbm_bytes_per_s": 819e9})


@pytest.fixture
def root():
    return ROOT
