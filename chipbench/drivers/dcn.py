"""Driver of the DCN traffic sweep: ``repro.dcn.run_dcn_sweep`` and tables.

A spec is one ``DcnSpec`` of the configuration's fat tree, job and
placement variants, with the mix's fault ratios and snapshots per ratio.
The timed path is the engine call with ``backend="jax"`` and the mix's
tables (``traffic_tables``, ``cross_tor_curve``).

The check regenerates a seeded sample of snapshots from the benchmark's
own copy of the counter-threefry stream, places the job on each under
every variant with the plain reference, and compares pair counts,
feasibility and the constraint level; it then rebuilds every table of
every spec from the program's grids with the reference reduction and
compares every value.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from reference import fattree, tables, threefry

#: Numbers the check compares, each with its limit: counts and tables are
#: exact, so any difference fails.
LIMITS = {"grid_cells_off": 0, "table_values_off": 0}

_COUNTS = ("groups", "dp_pairs", "crossing_pairs", "crossing_pod_pairs",
           "feasible")


class Driver:
    def __init__(self, config: dict, traffic: dict):
        self.config = config
        self.traffic = traffic
        self.tps = [int(t) for t in config["tp_sizes"]]
        self.ratios = [float(r) for r in traffic["fault_ratios"]]
        gpus = config["num_nodes"] * config["gpus_per_node"]
        # the job: its share of the GPUs in whole TP groups, at least one
        self.jobs = [max(int(gpus * config["job_scale"]) // tp * tp, tp)
                     for tp in self.tps]

    def spec(self, seed: int, index: int):
        from repro.sim import DcnSpec
        c = self.config
        return DcnSpec(num_nodes=c["num_nodes"],
                       fault_ratios=tuple(self.ratios),
                       samples=self.traffic["samples"], seed=seed,
                       tp_sizes=tuple(self.tps), job_scale=c["job_scale"],
                       variants=tuple(c["variants"]),
                       gpus_per_node=c["gpus_per_node"],
                       nodes_per_tor=c["nodes_per_tor"],
                       agg_domain=c["agg_domain"], k=c["k"],
                       greedy_seed=c["greedy_seed"])

    def warm_spec(self, seed: int):
        """A whole spec: its fault-ratio rows form the one block the
        window's specs run."""
        return self.spec(seed, 0)

    def snapshots(self, spec) -> int:
        return len(spec.fault_ratios) * spec.samples

    def run(self, spec):
        """The timed path: engine call, then the mix's tables."""
        import jax
        from repro.dcn.tables import cross_tor_curve, traffic_tables
        from repro.sim import run_dcn_sweep
        with jax.profiler.TraceAnnotation("chipbench.engine"):
            result = run_dcn_sweep(spec, backend="jax",
                                   chunk_snapshots=self.traffic["block"])
        with jax.profiler.TraceAnnotation("chipbench.table"):
            out = {}
            if "traffic_tables" in self.traffic["tables"]:
                out["traffic_tables"] = traffic_tables(result)
            if "cross_tor_curve" in self.traffic["tables"]:
                out["cross_tor_curve"] = cross_tor_curve(result)
        return result, out

    def _tables(self, result, ft) -> Dict[str, object]:
        grids = {k: getattr(result, k) for k in _COUNTS + ("n_constraints",)}
        rows = tables.traffic_table(
            self.config["variants"], self.ratios, self.tps,
            [tp // self.config["gpus_per_node"] for tp in self.tps], grids,
            self.config["traffic_model"], ft=ft)
        want: Dict[str, object] = {}
        if "traffic_tables" in self.traffic["tables"]:
            want["traffic_tables"] = rows
        if "cross_tor_curve" in self.traffic["tables"]:
            want["cross_tor_curve"] = tables.cross_tor_curve(rows,
                                                             self.tps[0])
        return want

    def _row(self, spec, ri: int, row: int) -> List[Dict[str, int]]:
        """Reference counts of one snapshot: ``[tp][variant] -> counts``."""
        mask = threefry.fault_masks(self.config["num_nodes"], self.ratios[ri],
                                    spec.seed + ri, np.asarray([row]))[0]
        faults = set(np.flatnonzero(mask).tolist())
        return [[fattree.evaluate(faults, self.config, v, tp, job)
                 for v in self.config["variants"]]
                for tp, job in zip(self.tps, self.jobs)]

    def check(self, done, rng: np.random.Generator,
              control: bool = False) -> Dict[str, int]:
        """Compare what the window's specs produced with the reference.

        ``control`` puts the reference itself in the program's place,
        computed one precision step down (int16 counts, float32 tables).
        """
        samples = self.traffic["samples"]
        flat = [(i, ri, r) for i, s in enumerate(done)
                for ri in range(len(self.ratios)) for r in range(samples)]
        take = rng.choice(len(flat), size=min(self.traffic["check_rows"],
                                              len(flat)), replace=False)
        grid_off = 0
        for j in sorted(take):
            i, ri, row = flat[j]
            result = done[i].output[0]
            want = self._row(done[i].spec, ri, row)
            for ti, per_variant in enumerate(want):
                for vi, ref in enumerate(per_variant):
                    if control:
                        got = {k: int(np.asarray(v).astype(np.int16))
                               for k, v in ref.items()}
                    else:
                        got = {k: int(getattr(result, k)[vi, ri, row, ti])
                               for k in _COUNTS}
                        got["n_constraints"] = (
                            int(result.n_constraints[ri, row, ti])
                            if self.config["variants"][vi] == "orchestrated"
                            else -1)
                    grid_off += sum(got[k] != v for k, v in ref.items())
        table_off = 0
        for s in done:
            result, made = s.output
            want = self._tables(result, np.float64)
            got = self._tables(result, np.float32) if control else made
            table_off += tables.count_off(got, want)
        return {"grid_cells_off": grid_off, "table_values_off": table_off}
