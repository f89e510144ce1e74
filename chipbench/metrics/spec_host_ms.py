"""Host time of a spec outside the mask source and the grid evaluation:
each spec's wall time minus its ``prng.counter_fault_masks`` and
``sim.jax.eval_block`` spans (engine set-up, ``run_sweep`` glue, the
table reductions), averaged over the window's specs."""

_INNER = ("prng.counter_fault_masks", "sim.jax.eval_block")


def read(r):
    inner = [s for s in r.spans if s.name in _INNER]
    if not inner or not r.window.specs:
        return None
    rest = []
    for spec in r.window.specs:
        covered = sum(s.dur_ns for s in inner
                      if spec.start_ns <= s.start_ns < spec.end_ns)
        rest.append(spec.end_ns - spec.start_ns - covered)
    return sum(rest) / len(rest) / 1e6
