"""The plain references agree with the program on small sizes, and with a
second witness: the program's own scalar (per-snapshot) paths."""

import json

import numpy as np
import pytest
from conftest import BENCH
from reference import fattree, hbd, tables, threefry

FLEET = json.loads((BENCH / "configs" / "fleet131k.json").read_text())
ARCHS = FLEET["architectures"]
DCN = json.loads((BENCH / "configs" / "dcn2048.json").read_text())


@pytest.mark.parametrize("nodes,ratio,seed", [
    (1000, 0.05, 0), (777, 0.2, 2**40 + 3), (4096, 0.0233, 2**62 + 11),
    (5, 1.0, 9), (64, 0.0, 1)])
def test_mask_stream(nodes, ratio, seed):
    from repro.core.prng import counter_fault_masks
    want = counter_fault_masks(nodes, ratio, 12, seed)
    rows = np.array([11, 0, 5])
    assert (threefry.fault_masks(nodes, ratio, seed, rows) == want[rows]).all()


@pytest.mark.parametrize("nodes,ratio", [(1024, 0.03), (700, 0.2),
                                         (2048, 0.08)])
def test_architectures_match_the_program(nodes, ratio):
    from repro.sim import CounterIIDSnapshots, ScenarioSpec, run_sweep
    spec = ScenarioSpec(nodes, CounterIIDSnapshots(ratio, 24, seed=5),
                        (16, 32, 64, 128), tuple(a["name"] for a in ARCHS))
    res = run_sweep(spec, backend="numpy")
    masks = threefry.fault_masks(nodes, ratio, 5, np.arange(24))
    total, faulty, placed = hbd.evaluate(ARCHS, masks, (16, 32, 64, 128), 4)
    assert (total == res.total_gpus).all()
    assert (faulty == res.faulty_gpus).all()
    assert (placed == res.placed_gpus).all()


def test_architectures_match_the_scalar_witness():
    from repro.sim import make_model
    masks = threefry.fault_masks(512, 0.1, 3, np.arange(6))
    total, faulty, placed = hbd.evaluate(ARCHS, masks, (16, 32, 128), 4)
    for ai, a in enumerate(ARCHS):
        model = make_model(a["name"], 512, 4)
        for s in range(6):
            faults = set(np.flatnonzero(masks[s]).tolist())
            for ti, tp in enumerate((16, 32, 128)):
                r = model.evaluate(faults, tp)
                assert (r.total_gpus, r.faulty_gpus, r.placed_gpus) == (
                    total[ai, ti], faulty[ai, s, ti], placed[ai, s, ti]), \
                    (a["name"], s, tp)


def test_sweep_tables_match_the_program():
    from repro.sim import (CounterIIDSnapshots, ScenarioSpec, max_job_table,
                           run_sweep, waste_table)
    names = tuple(a["name"] for a in ARCHS)
    res = run_sweep(ScenarioSpec(1024, CounterIIDSnapshots(0.05, 40, seed=8),
                                 (16, 32, 64), names), backend="numpy")
    grids = (res.total_gpus, res.faulty_gpus, res.placed_gpus)
    assert tables.count_off(waste_table(res), tables.waste_table(
        names, (16, 32, 64), *grids)) == 0
    assert tables.count_off(max_job_table(res, 5.0), tables.max_job_table(
        names, (16, 32, 64), grids[0], grids[2], 5.0)) == 0


@pytest.mark.parametrize("nodes,agg,tps", [(512, 128, (32,)),
                                           (256, 64, (16, 32))])
def test_fat_tree_matches_the_program(nodes, agg, tps):
    from repro.dcn.engine import run_dcn_sweep_scalar
    from repro.dcn.tables import cross_tor_curve, traffic_tables
    from repro.sim import DcnSpec, run_dcn_sweep
    cfg = {**DCN, "num_nodes": nodes, "agg_domain": agg}
    spec = DcnSpec(num_nodes=nodes, fault_ratios=(0.0, 0.03, 0.07, 0.2),
                   samples=5, seed=2**35 + 1, tp_sizes=tps, job_scale=0.85,
                   agg_domain=agg)
    res = run_dcn_sweep(spec, backend="numpy")
    witness = run_dcn_sweep_scalar(spec)
    keys = ("groups", "dp_pairs", "crossing_pairs", "crossing_pod_pairs",
            "feasible")
    for ri, ratio in enumerate(spec.fault_ratios):
        masks = threefry.fault_masks(nodes, ratio, spec.seed + ri,
                                     np.arange(5))
        for s in range(5):
            faults = set(np.flatnonzero(masks[s]).tolist())
            for ti, tp in enumerate(tps):
                job = spec.job_gpus(tp)
                for vi, v in enumerate(spec.variants):
                    ref = fattree.evaluate(faults, cfg, v, tp, job)
                    for k in keys:
                        assert ref[k] == getattr(res, k)[vi, ri, s, ti]
                        assert ref[k] == getattr(witness, k)[vi, ri, s, ti]
                    if v == "orchestrated":
                        assert ref["n_constraints"] == \
                            res.n_constraints[ri, s, ti]
    grids = {k: getattr(res, k) for k in keys + ("n_constraints",)}
    rows = tables.traffic_table(spec.variants, spec.fault_ratios, tps,
                                [tp // 4 for tp in tps], grids,
                                DCN["traffic_model"])
    assert tables.count_off(traffic_tables(res), rows) == 0
    assert tables.count_off(cross_tor_curve(res),
                            tables.cross_tor_curve(rows, tps[0])) == 0


def test_count_off():
    want = [{"a": 1.0, "b": None}, {"a": 2.0, "b": 0.5}]
    assert tables.count_off([dict(r) for r in want], want) == 0
    assert tables.count_off([{"a": 1.0, "b": 0.0}, want[1]], want) == 1
    assert tables.count_off(want[:1], want) == 2
    assert tables.count_off({0.0: 1.0}, {0.0: 1.0, 0.5: 2.0}) == 1
