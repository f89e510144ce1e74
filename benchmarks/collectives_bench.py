"""Paper §5.2 (ring AllReduce utilization) + Appendix G (Binary Exchange).

Wall-clock timings for ring-vs-native collectives on 8 forced host devices
(relative numbers; absolute bandwidth is CPU-bound) plus the analytic wire
cost model at production scale: ring AllReduce 2X(n-1)/n vs the Binary
Exchange all-to-all (n/2 log n slabs) vs sequential ring all-to-all O(n^2).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

from .common import row

ROOT = Path(__file__).resolve().parents[1]

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, time
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.parallel.collectives import (ring_all_reduce,
    binary_exchange_all_to_all, all_to_all_baseline)
from repro.runtime import make_mesh

mesh = make_mesh((8,), ("model",))
x = jax.random.normal(jax.random.PRNGKey(0), (8, 1024, 256))
sm = lambda f: jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("model"),
                                     out_specs=P("model")))
out = {}
for name, fn in [
    ("ring_allreduce", sm(lambda v: ring_all_reduce(v, "model", impl="ring"))),
    ("psum_allreduce", sm(lambda v: ring_all_reduce(v, "model", impl="psum"))),
]:
    fn(x).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(10):
        fn(x).block_until_ready()
    out[name] = (time.perf_counter() - t0) / 10 * 1e6

y = jax.random.normal(jax.random.PRNGKey(1), (8, 8, 4096))
for name, fn in [
    ("binary_exchange_a2a", sm(lambda v: binary_exchange_all_to_all(v[0], "model")[None])),
    ("xla_all_to_all", sm(lambda v: all_to_all_baseline(v[0], "model")[None])),
]:
    fn(y).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(10):
        fn(y).block_until_ready()
    out[name] = (time.perf_counter() - t0) / 10 * 1e6
print(json.dumps(out))
"""


def run():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env.pop("XLA_FLAGS", None)
    # the child measures 8 forced host devices, and an accelerator belongs
    # to one process: the parent (``benchmarks.run``) may hold it already
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "-c", _CHILD], capture_output=True,
                         text=True, env=env, timeout=600)
    if res.returncode == 0:
        out = json.loads(res.stdout.strip().splitlines()[-1])
        for name, us in out.items():
            row(f"collective/{name}", us, "8dev-cpu-relative")
    else:
        row("collective/error", 0.0, res.stderr[-200:])

    # analytic wire model at ring size p (per-GPU bytes, unit message m=1)
    for p in (8, 16, 32, 64):
        ring_ar = 2 * (p - 1) / p
        ring_a2a = p * (p - 1) / 2 / p          # O(p) per GPU hops x slabs
        import math
        be_a2a = 0.5 * math.log2(p)             # n/2 slabs x log2 rounds / n
        row(f"wire_model/p{p}", 0.0,
            {"ring_allreduce": round(ring_ar, 3),
             "ring_a2a_O(p2)": round(ring_a2a, 3),
             "binary_exchange_a2a": round(be_a2a, 3),
             "paper": "App G: O(p^2) -> O(p log p)"})


if __name__ == "__main__":
    run()
