"""Driver of the scenario sweep: ``repro.sim.run_sweep`` and its tables.

A spec is one ``ScenarioSpec`` of the configuration's cluster,
architectures and TP sizes, with ``CounterIIDSnapshots`` of the mix's
size; the ``index``-th spec of a run takes the mix's fault ratios in turn.
The timed path is the engine call with ``backend="jax"`` and the mix's
tables (``waste_table``, ``max_job_table``).

The check regenerates a seeded sample of snapshots from the benchmark's
own copy of the counter-threefry stream, evaluates every architecture on
them with the plain reference, and compares every grid cell; it then
rebuilds every table of every spec from the program's grids with the
reference reductions and compares every value.
"""

from __future__ import annotations

import dataclasses
from collections import defaultdict
from typing import Dict, List

import numpy as np

from reference import hbd, tables, threefry

#: Numbers the check compares, each with its limit: grids and tables are
#: exact, so any difference fails.
LIMITS = {"grid_cells_off": 0, "table_values_off": 0}


def _cells_off(got, want) -> int:
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape:
        return int(want.size)
    return int(np.count_nonzero(got != want))


class Driver:
    def __init__(self, config: dict, traffic: dict):
        self.config = config
        self.traffic = traffic
        self.archs = config["architectures"]
        self.names = [a["name"] for a in self.archs]
        self.tps = [int(t) for t in config["tp_sizes"]]

    def spec(self, seed: int, index: int):
        from repro.sim import CounterIIDSnapshots, ScenarioSpec
        ratios = self.traffic["fault_ratios"]
        return ScenarioSpec(
            num_nodes=self.config["num_nodes"],
            snapshots=CounterIIDSnapshots(
                fault_ratio=ratios[index % len(ratios)],
                samples=self.traffic["snapshots"], seed=seed),
            tp_sizes=tuple(self.tps), architectures=tuple(self.names),
            gpus_per_node=self.config["gpus_per_node"])

    def warm_spec(self, seed: int):
        """One block of a spec: it compiles the program every block of the
        window runs, without drawing the rest of the spec."""
        spec = self.spec(seed, 0)
        rows = min(self.traffic["snapshots"], self.traffic["block"])
        return dataclasses.replace(spec, snapshots=dataclasses.replace(
            spec.snapshots, samples=rows))

    def snapshots(self, spec) -> int:
        return spec.snapshots.samples

    def run(self, spec):
        """The timed path: engine call, then the mix's tables."""
        import jax
        from repro.sim import max_job_table, run_sweep, waste_table
        with jax.profiler.TraceAnnotation("chipbench.engine"):
            result = run_sweep(spec, backend="jax",
                               chunk_snapshots=self.traffic["block"])
        with jax.profiler.TraceAnnotation("chipbench.table"):
            out = {}
            if "waste_table" in self.traffic["tables"]:
                out["waste_table"] = waste_table(result)
            if "max_job_table" in self.traffic["tables"]:
                out["max_job_table"] = max_job_table(
                    result, **self.traffic["tables"]["max_job_table"])
        return result, out

    def _tables(self, result, ft) -> Dict[str, List[dict]]:
        want = {}
        grids = (result.total_gpus, result.faulty_gpus, result.placed_gpus)
        if "waste_table" in self.traffic["tables"]:
            want["waste_table"] = tables.waste_table(self.names, self.tps,
                                                     *grids, ft=ft)
        if "max_job_table" in self.traffic["tables"]:
            want["max_job_table"] = tables.max_job_table(
                self.names, self.tps, grids[0], grids[2], ft=ft,
                **self.traffic["tables"]["max_job_table"])
        return want

    def check(self, done, rng: np.random.Generator,
              control: bool = False) -> Dict[str, int]:
        """Compare what the window's specs produced with the reference.

        ``control`` puts the reference itself in the program's place,
        computed one precision step down (int16 grids, float32 tables).
        """
        flat = [(i, r) for i, s in enumerate(done) for r in range(s.snapshots)]
        take = rng.choice(len(flat), size=min(self.traffic["check_rows"],
                                              len(flat)), replace=False)
        rows = defaultdict(list)
        for j in sorted(take):
            rows[flat[j][0]].append(flat[j][1])
        grid_off = 0
        g = self.config["gpus_per_node"]
        for i, picked in rows.items():
            spec, (result, _) = done[i].spec, done[i].output
            picked = np.asarray(picked)
            masks = threefry.fault_masks(self.config["num_nodes"],
                                         spec.snapshots.fault_ratio,
                                         spec.snapshots.seed, picked)
            want = hbd.evaluate(self.archs, masks, self.tps, g)
            if control:
                got = hbd.evaluate(self.archs, masks, self.tps, g,
                                   dt=np.int16)
            else:
                got = (result.total_gpus, result.faulty_gpus[:, picked],
                       result.placed_gpus[:, picked])
            grid_off += sum(_cells_off(a, b) for a, b in zip(got, want))
            grid_off += _cells_off(list(result.names), self.names)
        table_off = 0
        for s in done:
            result, made = s.output
            want = self._tables(result, np.float64)
            got = self._tables(result, np.float32) if control else made
            table_off += tables.count_off(got, want)
        return {"grid_cells_off": grid_off, "table_values_off": table_off}
