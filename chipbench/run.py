#!/usr/bin/env python3
"""Chip benchmark of the fault-scenario engines: one cell, one run.

Usage (from the root of a checkout, on a machine with the cell's TPUs)::

    python3 chipbench/run.py --workload fleet131k-bulk --seed 7 \\
        --seconds 30 --trace 0

Runs the cell of ``BENCHMARK.json`` named by ``--workload``: set-up (the
cell's shapes compiled or loaded from ``chipbench/.jax_cache``), then a
closed loop of scenario specs for ``--seconds``, then the check of what the
timed path produced against the plain reference under
``chipbench/reference``.  ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` runs the window under the JAX profiler and reports
its per-layer metrics.  The last line of standard output is one JSON
object (``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
``breakdown`` with ``--trace 1``, and ``checks``: every compared number
with its limit).  Without a TPU, or with fewer than the cell asks for, it
exits with 2 and prints no result.
"""

import time

T0_NS = time.perf_counter_ns()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from harness import runner
    return runner.run(HERE.parent, args.workload, args.seed, args.seconds,
                      bool(args.trace), T0_NS)


if __name__ == "__main__":
    sys.exit(main())
