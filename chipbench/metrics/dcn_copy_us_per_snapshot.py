"""Host time of the DCN program's copies per snapshot: the ``repro.obs``
spans ``dcn.jax.put`` (host to device) and ``dcn.jax.fetch`` (the
placements back to the host, after the program) of the window over the
snapshots the window carried."""

_COPIES = ("dcn.jax.put", "dcn.jax.fetch")


def read(r):
    spans = [s for s in r.spans if s.name in _COPIES]
    if not spans or not r.window.snapshots:
        return None
    return sum(s.dur_ns for s in spans) / 1e3 / r.window.snapshots
