"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: per chip, the device's busy time, the time of each step
program and kernel, and its idle gaps labelled by the benchmark's host
span open at the time.

The traced window is the host span ``WINDOW_SPAN``, which the harness
opens right after the profiler starts and closes right before it
stops. Device events are clipped to it. Busy time is the union of the
intervals in which an operation ran (the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane). Host spans are the ``TraceAnnotation`` events
whose names start with ``SPAN_PREFIX``, all written from the
benchmark's files.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

CHIP_PLANE = re.compile(r"^/device:TPU:\d+$")
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NO_SPAN = "(no span)"

Interval = Tuple[str, float, float]     # name, start_s, end_s


@dataclass
class Chip:
    name: str
    ops: List[Interval] = field(default_factory=list)
    modules: List[Interval] = field(default_factory=list)


@dataclass
class Trace:
    lo: float                   # window, seconds on the trace's clock
    hi: float
    chips: List[Chip]
    spans: List[Interval]       # the benchmark's host spans

    @property
    def window_s(self) -> float:
        return self.hi - self.lo


def _clip(evs: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    out = []
    for name, s, e in evs:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((name, s, e))
    return sorted(out, key=lambda x: x[1])


def from_events(window: Tuple[float, float], devices: Dict[str, Dict],
                spans: Sequence[Interval]) -> Trace:
    """A Trace from plain intervals: ``devices`` maps a chip's name to
    {"ops": [...], "modules": [...]}; everything is clipped to
    ``window``."""
    lo, hi = window
    chips = [Chip(n, _clip(d.get("ops", ()), lo, hi),
                  _clip(d.get("modules", ()), lo, hi))
             for n, d in sorted(devices.items())]
    return Trace(lo, hi, chips, sorted(spans, key=lambda x: x[1]))


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` with the profiler's own reader."""
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path))


def from_profile(pd) -> Trace:
    """A Trace from a ``jax.profiler.ProfileData``."""
    devices: Dict[str, Dict] = {}
    spans: List[Interval] = []
    window = None
    for plane in pd.planes:
        if CHIP_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            d = devices.setdefault(plane.name, {})
            for key, line in (("ops", OPS_LINE), ("modules", MODULES_LINE)):
                if line in lines:
                    d[key] = [(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                              for e in lines[line].events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if not e.name.startswith(SPAN_PREFIX):
                        continue
                    iv = (e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                    if e.name == WINDOW_SPAN:
                        window = iv[1:]
                    else:
                        spans.append(iv)
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    return from_events(window, devices, spans)


def union_s(evs: Sequence[Interval]) -> float:
    """Length of the union of the intervals (sorted by start)."""
    total, cur_s, cur_e = 0.0, None, None
    for _, s, e in sorted(evs, key=lambda x: x[1]):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(chip: Chip, lo: float, hi: float) -> List[Tuple[float, float]]:
    """Intervals of [lo, hi] in which no operation ran on ``chip``."""
    out, t = [], lo
    for _, s, e in chip.ops:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label(t: float, spans: Sequence[Interval]) -> str:
    """The innermost (latest-starting) host span open at ``t``."""
    best = None
    for name, s, e in spans:
        if s > t:
            break
        if e >= t:
            best = name
    return best or NO_SPAN


def busy_s(trace: Trace) -> float:
    """Device busy seconds, mean over the chips."""
    if not trace.chips:
        return 0.0
    return sum(union_s(c.ops) for c in trace.chips) / len(trace.chips)


def idle_share(trace: Trace) -> Optional[float]:
    if not trace.chips or trace.window_s <= 0:
        return None
    return 1.0 - busy_s(trace) / trace.window_s


def matching(evs: Sequence[Interval], patterns: Sequence[str]
             ) -> List[Interval]:
    rx = [re.compile(p) for p in patterns]
    return [e for e in evs if any(r.search(e[0]) for r in rx)]


def op_kind(name: str) -> str:
    """An HLO op event's kind and result shape, without its number and
    layouts: ``%copy.1155.remat = bf16[1033,128,5,64]{3,2,1,0} copy(..``
    -> ``copy.remat bf16[1033,128,5,64]``; the same op of every layer
    then adds up under one name."""
    head, _, rest = name.partition(" = ")
    base = re.sub(r"\.\d+", "", head.lstrip("%"))
    m = re.match(r"(.*?)\s+[\w\-]+\(", rest)
    shape = re.sub(r"\{[^}]*\}|\s", "", m.group(1) if m else rest)
    return f"{base} {shape}".strip()


def breakdown(trace: Trace, top: int = 10) -> Dict[str, List]:
    """The device operations that took most time (summed over chips and
    layers by ``op_kind``) and the idle time grouped by the host span
    open at each gap's midpoint, both longest first."""
    ops: Dict[str, float] = {}
    idle: Dict[str, float] = {}
    for c in trace.chips:
        for name, s, e in c.ops:
            k = op_kind(name)
            ops[k] = ops.get(k, 0.0) + (e - s)
        for s, e in gaps(c, trace.lo, trace.hi):
            k = label((s + e) / 2, trace.spans)
            idle[k] = idle.get(k, 0.0) + (e - s)
    rank = lambda d: [[k, v] for k, v in
                      sorted(d.items(), key=lambda x: -x[1])[:top]]
    return {"device_ops": rank(ops), "idle_gaps": rank(idle)}
