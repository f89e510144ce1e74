"""Share of the sweep grid program's roofline, from the device trace.

The program is memory-bound.  Its least traffic is one bit of fault mask
per node per snapshot in, and the int32 faulty and placed GPU counts per
architecture and TP size out; over the chip's HBM bandwidth that is the
least time.  The share is the least time over the device time of the grid
program's executions in the window (the ``XLA Modules`` events named
below, summed over the cell's chips).  The least traffic counts the work,
not how the program lays it out, so no layout can lift it past 100%.
"""

#: Module of ``repro.sim.jax_backend._grid_fn`` on the device, as the
#: profiler names it: the jitted, vmapped per-snapshot evaluator.
MODULE = "jit_eval_mask"


def least_bytes(rows: int, nodes: int, architectures: int, tps: int) -> float:
    return rows * nodes / 8 + rows * architectures * 2 * tps * 4


def read(r):
    rows = sum((s.attrs or {}).get("rows", 0) for s in r.spans
               if s.name == "sim.jax.eval_block")
    device_s = sum(v for k, v in (r.trace or {}).get("modules", {}).items()
                   if k.startswith(MODULE))
    if not rows or device_s <= 0:
        return None
    c = r.config
    least_s = least_bytes(rows, c["num_nodes"], len(c["architectures"]),
                          len(c["tp_sizes"])) / r.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / device_s
