"""Host time of the DCN pair counts per snapshot: the ``repro.obs`` spans
``dcn.pair_counts`` of the window (one per variant and TP size) over the
snapshots the window carried."""


def read(r):
    spans = [s for s in r.spans if s.name == "dcn.pair_counts"]
    if not spans or not r.window.snapshots:
        return None
    return sum(s.dur_ns for s in spans) / 1e3 / r.window.snapshots
