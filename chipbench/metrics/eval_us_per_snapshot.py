"""Time of the sweep's grid evaluation per snapshot: the ``repro.obs``
spans ``sim.jax.eval_block`` of the window (host to device copy, device
program, device to host copy) over the rows they evaluated."""


def read(r):
    spans = [s for s in r.spans if s.name == "sim.jax.eval_block"]
    rows = sum((s.attrs or {}).get("rows", 0) for s in spans)
    if not rows:
        return None
    return sum(s.dur_ns for s in spans) / 1e3 / rows
