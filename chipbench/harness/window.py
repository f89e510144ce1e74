"""The measured window: one architect in a closed loop, and its statistics.

The architect submits a spec, waits for its finished tables, and submits
the next, until the window's length has passed; the spec in flight at
that moment finishes and belongs to the window.  So the window ends when
its last spec does, and every rate below is all the work over all the
time, stalls included.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, List

import numpy as np


@dataclasses.dataclass
class Spec:
    """One submitted spec: its index, timing and what the timed path made."""

    index: int
    start_ns: int
    end_ns: int
    snapshots: int
    spec: Any = None
    output: Any = None

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


@dataclasses.dataclass
class Window:
    start_ns: int
    end_ns: int
    specs: List[Spec]

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9

    @property
    def snapshots(self) -> int:
        return sum(s.snapshots for s in self.specs)


def closed_loop(submit: Callable[[int], Spec], seconds: float,
                clock: Callable[[], int] = time.perf_counter_ns) -> Window:
    """Run ``submit(i)`` for i = 0, 1, ... while the window is open."""
    start = clock()
    deadline = start + int(seconds * 1e9)
    specs: List[Spec] = []
    while not specs or specs[-1].end_ns < deadline:
        specs.append(submit(len(specs)))
    return Window(start, specs[-1].end_ns, specs)


def snapshots_per_s(window: Window) -> float:
    return window.snapshots / window.seconds


def spec_p95_ms(window: Window) -> float:
    """95th percentile (linear interpolation) of spec-to-table time."""
    return float(np.percentile([s.seconds for s in window.specs], 95)) * 1e3


class CompileCounter:
    """Counts JAX compilations (backend compiles and persistent-cache
    loads) and jaxpr traces while it is active."""

    _COMPILE = ("/jax/core/compile/backend_compile_duration",
                "/jax/compilation_cache/cache_retrieval_time_sec")
    _TRACE = ("/jax/core/compile/jaxpr_trace_duration",)

    def __init__(self):
        self.compiles = 0
        self.traces = 0
        self.active = False
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if not self.active:
            return
        if event in self._COMPILE:
            self.compiles += 1
        elif event in self._TRACE:
            self.traces += 1
