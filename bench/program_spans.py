"""The program's own host spans in a traced run, for the per-layer
metrics that read them.

The program opens named spans on the profiler's clock
(``repro.serving.telemetry.HOST_SPANS``, all ``preble.*``): engine
spans carry the instance id as a suffix (``preble.engine.step.0``,
``preble.engine.fetch.0``, ...), and E2 opens ``preble.e2.schedule``.
``bench.trace`` keeps only the benchmark's own ``bench.*`` spans, so
this module reads the ``/host:`` planes of the newest ``.xplane.pb``
that ``bench/run.py`` wrote under ``TRACE_DIR`` once more, keeps the
``preble.*`` events and clips them to the traced window
(``run.trace.lo`` / ``hi``, the same clock). The device planes come
from ``run.trace`` alone. Each file is read once.

``of(run)`` returns None where the run was not traced, no profile is
there, or the program opens no such span (a program from before
them), and it never raises: a reader's failure must not fail a run.
"""

from __future__ import annotations

import functools
import sys
import traceback
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from bench.run import TRACE_DIR
from bench.trace import Interval

PREFIX = "preble."
ENGINE = "preble.engine."
E2_SCHEDULE = "preble.e2.schedule"

Pair = Tuple[float, float]


def newest() -> Optional[Path]:
    files = list(Path(TRACE_DIR).rglob("*.xplane.pb"))
    return max(files, key=lambda f: f.stat().st_mtime_ns) if files else None


@functools.lru_cache(maxsize=2)
def _load(path: str, mtime_ns: int, size: int) -> Tuple[Interval, ...]:
    """Every ``preble.*`` event of the profile's host planes, seconds
    on the trace's clock, in start order (``mtime_ns`` and ``size``
    key the memo to the file's contents)."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out.append((e.name, e.start_ns * 1e-9, e.end_ns * 1e-9))
    return tuple(sorted(out, key=lambda x: x[1]))


def of(run) -> Optional[List[Interval]]:
    """The program's spans that overlap the run's traced window,
    clipped to it, in start order; None where there are none."""
    try:
        if run.trace is None:
            return None
        path = newest()
        if path is None:
            return None
        st = path.stat()
        lo, hi = run.trace.lo, run.trace.hi
        out = [(n, max(s, lo), min(e, hi))
               for n, s, e in _load(str(path), st.st_mtime_ns, st.st_size)
               if e > lo and s < hi]
        return out or None
    except Exception:           # noqa: BLE001 -- the run goes on without
        print("bench: program spans not read:", file=sys.stderr)
        traceback.print_exc(file=sys.stderr)
        return None


def engines(spans: Sequence[Interval]
            ) -> Dict[str, Dict[str, List[Interval]]]:
    """Engine spans by instance id, then by kind (``step``,
    ``fetch``, ...), each list in start order."""
    out: Dict[str, Dict[str, List[Interval]]] = {}
    for iv in spans:
        if iv[0].startswith(ENGINE):
            kind, _, inst = iv[0][len(ENGINE):].rpartition(".")
            out.setdefault(inst, {}).setdefault(kind, []).append(iv)
    return out


def nest(parents: Sequence[Interval], children: Sequence[Interval]
         ) -> List[List[Interval]]:
    """For each parent (disjoint, in start order) the children (in
    start order) that lie inside it."""
    out, j = [], 0
    for _, lo, hi in parents:
        while j < len(children) and children[j][1] < lo:
            j += 1
        got = []
        while j < len(children) and children[j][1] <= hi:
            if children[j][2] <= hi:
                got.append(children[j])
            j += 1
        out.append(got)
    return out


def minus(spans: Sequence[Interval], holes: Sequence[Interval]
          ) -> List[Pair]:
    """The parts of ``spans`` (disjoint, in start order) outside
    ``holes`` (disjoint, in start order)."""
    out, j = [], 0
    for _, lo, hi in spans:
        while j < len(holes) and holes[j][2] <= lo:
            j += 1
        t, k = lo, j
        while k < len(holes) and holes[k][1] < hi:
            if holes[k][1] > t:
                out.append((t, holes[k][1]))
            t = max(t, holes[k][2])
            k += 1
        if hi > t:
            out.append((t, hi))
    return out


def overlap_s(a: Sequence[Pair], b: Sequence[Pair]) -> float:
    """Length of the intersection of two lists of disjoint intervals,
    each in start order."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total
