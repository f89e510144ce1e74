"""Host time of the interval-mask build per interval: the ``repro.obs``
spans ``churn.masks`` of the window over the intervals they built."""


def read(r):
    spans = [s for s in r.spans if s.name == "churn.masks"]
    rows = sum((s.attrs or {}).get("rows", 0) for s in spans)
    if not rows:
        return None
    return sum(s.dur_ns for s in spans) / 1e3 / rows
