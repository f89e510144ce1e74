"""Host time of the sweep's copies per snapshot: the ``repro.obs`` spans
``sim.jax.put`` (tail padding, host to device) and ``sim.jax.fetch``
(device to host after the program, int64 unpacking) of the window over the
rows of the blocks they copied."""


def read(r):
    puts = [s for s in r.spans if s.name == "sim.jax.put"]
    fetches = [s for s in r.spans if s.name == "sim.jax.fetch"]
    rows = sum((s.attrs or {}).get("rows", 0) for s in puts)
    if not rows or not fetches:
        return None
    return sum(s.dur_ns for s in puts + fetches) / 1e3 / rows
