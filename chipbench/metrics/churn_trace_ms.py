"""Host time of a spec's fault traces (generation, 4-GPU split, interval
edges): the ``repro.obs`` spans ``churn.trace`` that start inside each
spec, summed per spec and averaged over the window's specs."""


def read(r):
    spans = [s for s in r.spans if s.name == "churn.trace"]
    if not spans or not r.window.specs:
        return None
    per_spec = [sum(s.dur_ns for s in spans
                    if spec.start_ns <= s.start_ns < spec.end_ns)
                for spec in r.window.specs]
    return sum(per_spec) / len(per_spec) / 1e6
