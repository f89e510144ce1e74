"""Production meshes.

Single pod: (data=16, model=16) = 256 chips.  Multi-pod: (pod=2, data=16,
model=16) = 512 chips.  The model axis is the HBD (the OCSTrx ring domain);
data/pod are DCN axes.  ``make_orchestrated_production_mesh`` additionally
routes the device order through the HBD-DCN orchestrator so the model axis
follows live OCS rings (with faults bypassed).
"""

from __future__ import annotations

from typing import Optional, Set

import jax

from ..runtime import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_orchestrated_production_mesh(*, multi_pod: bool = False,
                                      faults: Optional[Set[int]] = None,
                                      gpus_per_node: int = 4, k: int = 3):
    """Device order decided by the paper's orchestrator (requires spare
    capacity when faults are present; raises InsufficientCapacityError
    otherwise)."""
    from repro.core.placement import make_orchestrated_mesh, plan_mesh
    devices = jax.devices()
    num_nodes = len(devices) // gpus_per_node
    pod = 2 if multi_pod else 1
    plan = plan_mesh(num_nodes, gpus_per_node, tp_size=16, dp_size=16,
                     pod_size=pod, faults=faults or set(), k=k)
    return make_orchestrated_mesh(plan, devices), plan
