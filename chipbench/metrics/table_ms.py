"""Host time of a spec's table reductions: the ``repro.obs`` spans
``sim.tables.*`` that start inside each spec, summed per spec and averaged
over the window's specs."""


def read(r):
    tables = [s for s in r.spans if s.name.startswith("sim.tables.")]
    if not tables or not r.window.specs:
        return None
    per_spec = [sum(s.dur_ns for s in tables
                    if spec.start_ns <= s.start_ns < spec.end_ns)
                for spec in r.window.specs]
    return sum(per_spec) / len(per_spec) / 1e6
