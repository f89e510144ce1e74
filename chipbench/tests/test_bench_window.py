"""The window's rate and tail are over all its work, stalls included."""

import pytest
from harness import window as win


class FakeClock:
    def __init__(self):
        self.t = 0

    def __call__(self):
        return self.t


def test_rate_and_p95_count_a_stall():
    clock = FakeClock()
    # 100 ms per spec of 256 snapshots; specs 7 and 20 stall for 2 s
    lengths = [100_000_000] * 40
    lengths[7] = lengths[20] = 2_000_000_000

    def submit(i):
        start = clock.t
        clock.t += lengths[i]
        return win.Spec(i, start, clock.t, 256)

    window = win.closed_loop(submit, 5.0, clock=clock)
    # spec 20 starts at 3.9 s, inside the window, and ends at 5.9 s
    assert len(window.specs) == 21
    assert window.seconds == pytest.approx(5.9)
    assert win.snapshots_per_s(window) == pytest.approx(21 * 256 / 5.9)
    # 21 specs: the linear p95 sits on the 20th of the sorted times, the
    # first of the two stalls
    assert win.spec_p95_ms(window) == pytest.approx(2000.0)


def test_spec_in_flight_at_deadline_belongs_to_window():
    clock = FakeClock()

    def submit(i):
        start = clock.t
        clock.t += 3_000_000_000
        return win.Spec(i, start, clock.t, 10)

    window = win.closed_loop(submit, 4.0, clock=clock)
    assert len(window.specs) == 2
    assert window.seconds == pytest.approx(6.0)
    assert win.snapshots_per_s(window) == pytest.approx(20 / 6.0)


def test_compile_counter_counts_only_new_programs():
    import jax
    import jax.numpy as jnp
    counter = win.CompileCounter()
    fn = jax.jit(lambda x: x * 3 + 1)
    fn(jnp.ones(7)).block_until_ready()
    counter.active = True
    fn(jnp.ones(7)).block_until_ready()          # cached: nothing new
    assert (counter.compiles, counter.traces) == (0, 0)
    fn(jnp.ones(9)).block_until_ready()          # a new shape compiles
    assert counter.compiles >= 1 and counter.traces >= 1
    counter.active = False
    seen = counter.compiles
    fn(jnp.ones(11)).block_until_ready()
    assert counter.compiles == seen
