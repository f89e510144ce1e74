"""Host time of a spec's engine set-up: the ``repro.obs`` spans
``sim.models`` (the architecture models) and ``sim.jax.setup`` (the grid
evaluator and its totals, once per block on the streamed path) that start
inside each spec, summed per spec and averaged over the window's specs."""

_SETUP = ("sim.models", "sim.jax.setup")


def read(r):
    setup = [s for s in r.spans if s.name in _SETUP]
    if not setup or not r.window.specs:
        return None
    per_spec = [sum(s.dur_ns for s in setup
                    if spec.start_ns <= s.start_ns < spec.end_ns)
                for spec in r.window.specs]
    return sum(per_spec) / len(per_spec) / 1e6
