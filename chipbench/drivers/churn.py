"""Driver of the Monte-Carlo churn replay: ``repro.churn.monte_carlo_replay``
and its tables.

A spec is a ``ChurnSpec`` of the configuration's fleet, architectures and
TP sizes over the mix's horizon, seeded by the harness, with the mix's
number of realizations.  The timed path generates every realization of the
Appendix-A process, streams its interval masks block by block through the
grid program (``engine="streamed"``, ``backend="jax"``) and reduces the
timelines to the mix's tables (``integrated_waste_table`` per realization,
the ensemble's ``summary_table``).  A spec's snapshots are the intervals
its realizations produced, read from the run's output.

The check regenerates every realization with the benchmark's own copy of
the generator and compares its interval edges exactly (the edges are the
time axis of the grids, so each edge that differs counts as a grid cell
off, and a realization with another number of intervals counts all of
its edges); it then turns a seeded sample of intervals into masks from
the reference's events, evaluates every architecture on them with the
plain reference and compares every grid cell; last it rebuilds every
table value from the program's grids and durations with the reference
reductions and compares them exactly.

The window must compile nothing, and a realization's last block has a
length of its own, so the driver refuses (exit 2) a program whose grid
evaluator does not pad a short block up to the stream's block shape.
"""

from __future__ import annotations

import inspect
from collections import defaultdict
from typing import Any, Dict, List

import numpy as np

from harness.cell import CellError
from reference import churn, hbd, tables, trace

#: Numbers the check compares, each with its limit: edges, grids and
#: tables are exact, so any difference fails.
LIMITS = {"grid_cells_off": 0, "table_values_off": 0}


class Spec:
    """One submitted spec: the churn experiment (a ``repro.churn.ChurnSpec``)
    and how many realizations of it to replay; ``intervals`` is set by the
    run that replays it."""

    def __init__(self, churn, realizations: int):
        self.churn = churn
        self.realizations = realizations
        self.intervals = 0


def _cells_off(got, want) -> int:
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape:
        return int(want.size)
    return int(np.count_nonzero(got != want))


class Driver:
    def __init__(self, config: dict, traffic: dict):
        from repro.sim.jax_backend import GridEvaluator
        if "rows" not in inspect.signature(GridEvaluator).parameters:
            raise CellError(
                "the program evaluates a mask stream's short last block in "
                "a shape of its own (GridEvaluator takes no block rows), so "
                "every spec of this cell would compile inside the window")
        self.config = config
        self.traffic = traffic
        self.archs = config["architectures"]
        self.names = [a["name"] for a in self.archs]
        self.tps = [int(t) for t in config["tp_sizes"]]

    def _spec(self, seed: int, horizon_h: float, realizations: int) -> Spec:
        from repro.churn import ChurnSpec
        return Spec(ChurnSpec(
            trace_nodes=self.config["trace_nodes"], horizon_h=horizon_h,
            convert_4gpu=True, tp_sizes=tuple(self.tps),
            architectures=tuple(self.names),
            gpus_per_node=self.config["gpus_per_node"],
            mean_repair_h=self.config["mean_repair_h"], seed=seed),
            realizations)

    def spec(self, seed: int, index: int) -> Spec:
        return self._spec(seed, self.traffic["horizon_h"],
                          self.traffic["realizations"])

    def warm_spec(self, seed: int) -> Spec:
        """One realization over a horizon of more than one block: it
        compiles the block shape every block of the window runs."""
        return self._spec(seed, self.traffic["warm_horizon_h"], 1)

    def snapshots(self, spec: Spec) -> int:
        return spec.intervals

    def run(self, spec: Spec):
        """The timed path: the streamed replay, then the mix's tables."""
        import jax
        from repro.churn import integrated_waste_table, monte_carlo_replay
        with jax.profiler.TraceAnnotation("chipbench.engine"):
            ensemble = monte_carlo_replay(
                spec.churn, spec.realizations, engine="streamed",
                backend="jax", chunk_snapshots=self.traffic["block"])
        with jax.profiler.TraceAnnotation("chipbench.table"):
            out = {}
            if "integrated_waste_table" in self.traffic["tables"]:
                out["integrated_waste_table"] = [
                    integrated_waste_table(tl) for tl in ensemble.timelines]
            if "summary_table" in self.traffic["tables"]:
                out["summary_table"] = ensemble.summary_table()
        spec.intervals = sum(tl.num_intervals for tl in ensemble.timelines)
        return ensemble, out

    def _events(self, spec: Spec, r: int):
        c = spec.churn
        return trace.realization(self.config["trace_nodes"], c.horizon_h,
                                 c.mean_repair_h, c.seed + r)

    def _tables(self, spec: Spec, timelines, ft) -> Dict[str, Any]:
        want = {}
        grids = [(tl.edges_h, spec.churn.horizon_h, tl.total_gpus,
                  tl.faulty_gpus, tl.placed_gpus) for tl in timelines]
        if "integrated_waste_table" in self.traffic["tables"]:
            want["integrated_waste_table"] = [
                churn.integrated_waste_table(self.names, self.tps, *g, ft=ft)
                for g in grids]
        if "summary_table" in self.traffic["tables"]:
            want["summary_table"] = churn.summary_table(self.names, self.tps,
                                                        grids, ft=ft)
        return want

    def check(self, done, rng: np.random.Generator,
              control: bool = False) -> Dict[str, int]:
        """Compare what the window's specs produced with the reference.

        ``control`` puts the reference itself in the program's place,
        computed one precision step down (float32 edges and tables, int16
        grids).
        """
        grid_off = 0
        events: Dict[tuple, tuple] = {}
        flat: List[tuple] = []
        for i, s in enumerate(done):
            ensemble, _ = s.output
            for r, tl in enumerate(ensemble.timelines):
                ev = self._events(s.spec, r)
                horizon_h = s.spec.churn.horizon_h
                want = trace.interval_edges(ev, horizon_h)
                got = (trace.interval_edges(ev, horizon_h, np.float32)
                       if control else tl.edges_h)
                grid_off += _cells_off(got, want)
                events[i, r] = (ev, want)
                flat += [(i, r, b) for b in range(tl.num_intervals)]
            grid_off += abs(len(ensemble.timelines) - s.spec.realizations)
        take = rng.choice(len(flat), size=min(self.traffic["check_intervals"],
                                              len(flat)), replace=False)
        picked = defaultdict(list)
        for j in sorted(take):
            i, r, b = flat[j]
            picked[i, r].append(b)
        g = self.config["gpus_per_node"]
        n = self.config["num_nodes"]
        for (i, r), rows in picked.items():
            tl = done[i].output[0].timelines[r]
            ev, edges = events[i, r]
            rows = np.asarray(rows)
            inside = rows < len(edges)
            masks = trace.faulty_masks(ev, edges[rows[inside]], n)
            want = hbd.evaluate(self.archs, masks, self.tps, g)
            if control:
                got = hbd.evaluate(self.archs, masks, self.tps, g,
                                   dt=np.int16)
            else:
                got = (tl.total_gpus, tl.faulty_gpus[:, rows[inside]],
                       tl.placed_gpus[:, rows[inside]])
            grid_off += sum(_cells_off(a, b) for a, b in zip(got, want))
            grid_off += int((~inside).sum()) * 2 * len(self.archs) * len(
                self.tps)
            grid_off += _cells_off(list(tl.names), self.names)
        table_off = 0
        for s in done:
            ensemble, made = s.output
            want = self._tables(s.spec, ensemble.timelines, np.float64)
            got = (self._tables(s.spec, ensemble.timelines, np.float32)
                   if control else made)
            table_off += sum(tables.count_off(got.get(k, []), w)
                             for k, w in want.items())
        return {"grid_cells_off": grid_off, "table_values_off": table_off}
