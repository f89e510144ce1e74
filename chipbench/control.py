#!/usr/bin/env python3
"""Readings that set the check's limits: the program's and the control's.

Usage (from the root of a checkout, on a machine with the cell's TPUs)::

    python3 chipbench/control.py --workload fleet131k-bulk \\
        --seconds 10 --seeds 101 102 103

One process sets the cell up once, then for each seed runs a window at
the cell's own size and load and prints one JSON line with every compared
number twice: ``program`` (what the timed path produced, against the
reference: the lower reading) and ``control`` (the reference itself in the
program's place, one precision step down: the upper reading).  The
benchmark's own runs never run the control.
"""

import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))


def readings(root: Path, cell, seeds, seconds: float):
    """``[{"seed", "specs", "program", "control"}]``, one per seed."""
    from harness import runner
    from repro.runtime import use_devices
    session = runner.Session(root, cell)
    out = []
    with use_devices(cell.chips):
        session.warm(seeds[0])
        for seed in seeds:
            window, _ = session.window(seed, seconds)
            out.append({"seed": seed, "specs": len(window.specs),
                        "program": session.check(window, seed),
                        "control": session.check(window, seed,
                                                 control=True)})
    return out


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from harness import cell as cellmod, runner
    try:
        cell = cellmod.load(HERE.parent, args.workload)
        runner.prepare_env(HERE.parent)
        runner.devices(cell.chips)
    except (cellmod.CellError, runner.NoChip) as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    t0 = time.perf_counter()
    for r in readings(HERE.parent, cell, args.seeds, args.seconds):
        print(json.dumps(r), flush=True)
    print(f"control: {len(args.seeds)} seeds in "
          f"{time.perf_counter() - t0:.1f} s", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
