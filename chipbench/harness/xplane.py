"""From a JAX profiler trace to device busy time, op totals and idle gaps.

:func:`extract` reads the ``.xplane.pb`` file that ``jax.profiler`` writes
and keeps what the reduction needs as plain lists: per TPU, the events of
its ``XLA Ops`` and ``XLA Modules`` lines, and the host's
``chipbench.*`` annotations.  :func:`reduce` works on that extract alone,
so it is tested on a small recorded trace without a chip.

The profiler's clock is not ``time.perf_counter_ns``.  The harness opens
the annotation ``chipbench.window`` and reads ``perf_counter_ns`` just
inside it; that pair maps every trace timestamp onto the harness's clock,
where the window and the program's ``repro.obs`` spans live.
"""

from __future__ import annotations

import glob
import re
from bisect import bisect_left, bisect_right
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

ANCHOR = "chipbench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_TPU = re.compile(r"^/device:TPU:(\d+)$")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_OP = re.compile(r"^(\([^()]*\)|\S+) ([\w-]+)\(")
_ELEMENT = re.compile(r"[a-z]+\d*\[")

Event = Tuple[str, float, float]        # name, start ns, duration ns


def extract(log_dir: str) -> dict:
    """The parts of the newest trace under ``log_dir`` that :func:`reduce`
    reads: ``{"devices": {id: {"ops": [...], "modules": [...]}}, "host":
    [...]}``, each event ``[name, start_ns, duration_ns]``."""
    import jax
    files = sorted(glob.glob(str(Path(log_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    out: dict = {"devices": {}, "host": []}
    for plane in data.planes:
        match = _TPU.match(plane.name)
        if match:
            lines = {line.name: line for line in plane.lines}
            out["devices"][match.group(1)] = {
                key: [[e.name, e.start_ns, e.duration_ns]
                      for e in lines[name].events] if name in lines else []
                for key, name in (("ops", OPS_LINE),
                                  ("modules", MODULES_LINE))}
        elif plane.name.startswith("/host:"):
            out["host"].extend([e.name, e.start_ns, e.duration_ns]
                               for line in plane.lines for e in line.events
                               if e.name.startswith("chipbench."))
    return out


def op_name(hlo: str) -> str:
    """An op's HLO text up to its opcode, without layouts or operands:
    ``%fusion.12 = s32[1024,3]{0,1:T(4,128)} fusion(...)`` becomes
    ``%fusion.12 = s32[1024,3] fusion``; a tuple result is shown by its
    arity."""
    head, eq, rest = hlo.partition(" = ")
    match = _OP.match(_LAYOUT.sub("", rest)) if eq else None
    if not match:
        return hlo
    shape, op = match.groups()
    if shape.startswith("("):
        shape = f"({len(_ELEMENT.findall(shape))}-tuple)"
    return f"{head} = {shape} {op}"


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return [(lo, hi) for lo, hi in merged]


def _clip(events: Sequence[Event], lo: float, hi: float, shift: float
          ) -> List[Tuple[str, float, float]]:
    out = []
    for name, start, dur in events:
        a, b = max(start + shift, lo), min(start + dur + shift, hi)
        if b > a:
            out.append((name, a, b))
    return out


class _Namer:
    """Names a time by the innermost (shortest) span that covers it."""

    def __init__(self, spans: Sequence[Tuple[str, float, float]]):
        self.edges = sorted({x for _, a, b in spans for x in (a, b)})
        self.names = ["outside any span"] * max(len(self.edges) - 1, 0)
        for name, a, b in sorted(spans, key=lambda s: s[1] - s[2]):
            i, j = bisect_left(self.edges, a), bisect_left(self.edges, b)
            self.names[i:j] = [name] * (j - i)

    def __call__(self, t: float) -> str:
        i = bisect_right(self.edges, t) - 1
        if 0 <= i < len(self.names):
            return self.names[i]
        return "outside any span"


def reduce(trace: dict, anchor_perf_ns: float, window: Tuple[float, float],
           chips: int, spans: Sequence[Tuple[str, float, float]] = (),
           top: int = 10) -> dict:
    """Device time inside ``window`` (harness clock, ns) of the first
    ``chips`` TPUs.

    Returns ``busy_s`` and ``window_s`` (busy: union of op intervals,
    averaged over the chips), ``modules`` (``{module: seconds}``, summed
    over the chips), ``device_ops`` and ``idle_gaps`` (``[[name,
    seconds]]``, at most ``top`` each, largest first, per chip on
    average).  An idle gap is named by the innermost of ``spans`` (harness
    clock) or host annotations that covers its midpoint.
    """
    anchors = [e for e in trace["host"] if e[0] == ANCHOR]
    if not anchors:
        raise ValueError(f"trace has no {ANCHOR!r} annotation")
    shift = anchor_perf_ns - anchors[0][1]
    lo, hi = window
    host = _clip([e for e in trace["host"] if e[0] != ANCHOR], lo, hi, shift)
    name_at = _Namer(list(spans) + host)
    ids = sorted(trace["devices"], key=int)[:chips]
    if len(ids) < chips:
        raise ValueError(f"trace holds {len(ids)} TPUs, the cell {chips}")
    busy = 0.0
    ops: Dict[str, float] = {}
    modules: Dict[str, float] = {}
    gaps: Dict[str, float] = {}
    for dev in ids:
        d = trace["devices"][dev]
        op_events = _clip(d["ops"], lo, hi, shift)
        for name, a, b in op_events:
            name = op_name(name)
            ops[name] = ops.get(name, 0.0) + (b - a) / 1e9
        for name, a, b in _clip(d["modules"], lo, hi, shift):
            modules[name] = modules.get(name, 0.0) + (b - a) / 1e9
        merged = _union([(a, b) for _, a, b in op_events])
        busy += sum(b - a for a, b in merged) / 1e9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                name = name_at((a + b) / 2)
                gaps[name] = gaps.get(name, 0.0) + (b - a) / 1e9

    def ranked(totals: Dict[str, float]) -> List[list]:
        return [[k, v / chips] for k, v in
                sorted(totals.items(), key=lambda kv: -kv[1])[:top]]

    return {"busy_s": busy / chips, "window_s": (hi - lo) / 1e9,
            "modules": modules, "device_ops": ranked(ops),
            "idle_gaps": ranked(gaps)}
