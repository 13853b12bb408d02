"""The open-loop load generator and the client-side clock.

``drive`` submits each request to ``ClusterRuntime.submit`` once its
due time has passed, steps the cluster with ``now`` read from the wall
clock, and after every ``ClusterRuntime.step`` stamps each request
whose ``output_tokens`` grew: first token, every later token, and
completion, all on the harness's clock. Latencies are timed from the
due time, so a stall also delays the requests queued behind it. When
no engine has work the harness sleeps until the next due time.
``HostWatch`` times the loop's passes, to tell a host stall's kind.
"""

from __future__ import annotations

import gc
import resource
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence

from bench.traffic import Prompt


@dataclass
class Flight:
    """One request as the client sees it. Times are seconds on the
    run's clock."""
    prompt: Prompt
    due: float
    request: object = None          # the program's Request
    submit: Optional[float] = None
    token_times: List[float] = field(default_factory=list)
    done: Optional[float] = None    # finished (or failed) at
    failed: bool = False


class Clock:
    """Seconds since the run's origin, on ``time.perf_counter``."""

    def __init__(self):
        self.origin = time.perf_counter()

    def __call__(self) -> float:
        return time.perf_counter() - self.origin

    @staticmethod
    def sleep(s: float) -> None:
        if s > 0:
            time.sleep(s)


class HostWatch:
    """The loop's longest pass (outside its sleeps), and in it the main
    thread's CPU time, the garbage collector's time and the process's
    involuntary context switches; also the collector's time and full
    collections over the whole loop. A stall spent in the collector is
    Python's; one with little CPU time was waiting (on the device, a
    lock, or the operating system's scheduler)."""

    def __init__(self):
        self.gc_s = 0.0
        self.gc_full = 0
        self.longest: Optional[Dict[str, float]] = None
        self._gc_t0: Optional[float] = None

    def _on_gc(self, phase: str, info: Dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self.gc_s += time.perf_counter() - self._gc_t0
            self.gc_full += info["generation"] == 2
            self._gc_t0 = None

    def __enter__(self) -> "HostWatch":
        gc.callbacks.append(self._on_gc)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self._on_gc)

    def mark(self) -> tuple:
        return (time.perf_counter(), time.thread_time(), self.gc_s,
                resource.getrusage(resource.RUSAGE_SELF).ru_nivcsw)

    def record(self, m0: tuple, at: float) -> None:
        m1 = self.mark()
        wall = m1[0] - m0[0]
        if self.longest is None or wall > self.longest["wall_s"]:
            self.longest = {"at_s": at, "wall_s": wall,
                            "cpu_s": m1[1] - m0[1], "gc_s": m1[2] - m0[2],
                            "involuntary_switches": m1[3] - m0[3]}


def _busy(cluster) -> bool:
    return any(e.depth > 0 for e in cluster.engines.values()
               if not e.failed)


def _observe(flights: List[Flight], t: float, finished_states) -> None:
    """Stamp new tokens and completions seen after a step; drop the
    settled flights from ``flights`` in place."""
    keep = []
    for f in flights:
        n = len(f.request.output_tokens)
        if n > len(f.token_times):
            f.token_times.extend([t] * (n - len(f.token_times)))
        st = f.request.state
        if st in finished_states:
            f.done = t
            f.failed = st is not finished_states[0]
        else:
            keep.append(f)
    flights[:] = keep


def drive(cluster, flights: Sequence[Flight], clock: Clock, *,
          window: Sequence[float], drain_limit: float, request_cls,
          finished_states, on_window_start: Optional[Callable] = None,
          on_window_end: Optional[Callable] = None,
          idle_wait: Callable[[float], None] = None,
          watch: Optional[HostWatch] = None) -> Dict[str, float]:
    """Serve ``flights`` (sorted by due time, absolute on ``clock``)
    open loop. Stops once the window has closed and every flight due in
    it has settled, or at ``drain_limit``. ``finished_states`` is
    (FINISHED, FAILED). Returns the loop's own counts; ``watch`` times
    each pass."""
    t0, t1 = window
    sleep = idle_wait or clock.sleep
    live: List[Flight] = []
    i, n = 0, len(flights)
    started = ended = False
    steps = 0
    measured = [f for f in flights if t0 <= f.due < t1]
    while True:
        now = clock()
        m0 = watch.mark() if watch is not None else None
        if not started and now >= t0:
            started = True
            if on_window_start is not None:
                on_window_start()
        while i < n and flights[i].due <= now:
            f = flights[i]
            f.request = request_cls(tokens=f.prompt.tokens,
                                    max_new_tokens=f.prompt.max_new_tokens,
                                    arrival_time=f.due)
            f.submit = clock()
            cluster.submit(f.request, f.submit)
            live.append(f)
            i += 1
        if not ended and now >= t1:
            ended = True
            if on_window_end is not None:
                on_window_end()
        if ended and (now >= drain_limit
                      or all(f.done is not None for f in measured)):
            break
        if _busy(cluster):
            cluster.step(clock())
            steps += 1
            _observe(live, clock(), finished_states)
            if watch is not None:
                watch.record(m0, now)
            continue
        _observe(live, clock(), finished_states)
        if watch is not None:
            watch.record(m0, now)
        wake = [t for t in (t0 if not started else None,
                            t1 if not ended else None,
                            flights[i].due if i < n else None,
                            drain_limit if ended else None)
                if t is not None]
        if not wake:
            break
        sleep(min(wake) - clock())
    return {"steps": steps, "submitted": i}


def fill(cluster, prompts: Sequence[Prompt], clock: Clock, *, request_cls,
         finished_states, limit_s: float, wave: int) -> int:
    """Warm the prefix cache: serve the prompts with one-token outputs,
    ``wave`` at a time in their order (so the cache's recency follows
    it), each wave until it has settled. Returns the number that
    failed."""
    t_end = clock() + limit_s
    failed = 0
    for k in range(0, len(prompts), wave):
        live = [Flight(p, clock()) for p in prompts[k:k + wave]]
        for f in live:
            f.request = request_cls(tokens=f.prompt.tokens,
                                    max_new_tokens=1, arrival_time=f.due)
            cluster.submit(f.request, clock())
        wave_flights = list(live)
        while live:
            if clock() > t_end:
                raise RuntimeError(f"cache warm-up did not settle within "
                                   f"{limit_s} s")
            cluster.step(clock())
            _observe(live, clock(), finished_states)
        failed += sum(f.failed for f in wave_flights)
    return failed
