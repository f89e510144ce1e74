"""Host time of a spec's churn tables (timeline slicing, time integration,
ensemble summary): the ``repro.obs`` spans ``churn.tables`` that start
inside each spec, summed per spec and averaged over the window's specs."""


def read(r):
    spans = [s for s in r.spans if s.name == "churn.tables"]
    if not spans or not r.window.specs:
        return None
    per_spec = [sum(s.dur_ns for s in spans
                    if spec.start_ns <= s.start_ns < spec.end_ns)
                for spec in r.window.specs]
    return sum(per_spec) / len(per_spec) / 1e6
