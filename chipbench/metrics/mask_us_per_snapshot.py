"""Host time of the mask source per snapshot drawn: the ``repro.obs`` spans
``prng.counter_fault_masks`` of the window over the snapshots they drew."""


def read(r):
    spans = [s for s in r.spans if s.name == "prng.counter_fault_masks"]
    drawn = sum((s.attrs or {}).get("samples", 0) for s in spans)
    if not drawn:
        return None
    return sum(s.dur_ns for s in spans) / 1e3 / drawn
