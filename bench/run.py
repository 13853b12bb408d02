#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``); ``bench/cells/<cell>.json`` holds its
fixed arrival rate, replicas, warm-up and drain, and the limits of its
correctness check. Metrics are ``bench/metrics/<metric>.py``.

The run builds a Preble cluster through the program's public calls
(weights from the seed, ``ClusterRuntime`` under E2, ``warmup``),
warms the prefix cache with the mix's prompts, ramps the open-loop
traffic up, then measures ``--seconds`` of it on the wall clock
(``bench/harness.py``). ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` profiles the window and reports its per-layer
metrics. Either way, after the window the served tokens of a sample of
requests are compared with the plain reference (``bench/check.py``).

It refuses, printing no result, where JAX finds no TPU, fewer chips
than the cell asks for, or a device kind missing from
``bench/peaks.json``. The last stdout line is one JSON object.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import gc
import importlib.util
import json
import math
import shutil
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import check, harness, model, traffic        # noqa: E402
from bench.trace import WINDOW_SPAN                      # noqa: E402
from bench.work import Step                              # noqa: E402

TRACE_DIR = ROOT / ".bench_trace"
FILL_LIMIT_S = 300.0
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


class Refused(SystemExit):
    """A run that must not produce a result."""

    def __init__(self, msg: str):
        print(f"bench: refused: {msg}", file=sys.stderr, flush=True)
        super().__init__(2)


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    mix: Dict
    params: Dict                 # bench/cells/<name>.json
    end_to_end: List[str]
    per_layer: List[str]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]

    def applies(m):
        return name in m.get("workloads", [name])
    return Cell(name, w["chips"], model.load_config(w["config"]),
                traffic.load_mix(w["traffic"]),
                json.loads((BENCH / "cells" / f"{name}.json").read_text()),
                [m["name"] for m in spec["end_to_end"] if applies(m)],
                [m["name"] for m in spec["per_layer"] if applies(m)])


def load_peaks(kind: str) -> Dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise Refused(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def find_chips(n: int):
    """The first ``n`` TPU chips, or a refusal."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"JAX found platform {devs[0].platform!r}, not a TPU")
    if len(devs) < n:
        raise Refused(f"the cell needs {n} chips, JAX sees {len(devs)}")
    return devs[:n]


def read_metric(name: str, run) -> Optional[float]:
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


@dataclass
class Run:
    """What the metric readers read (``bench/metrics``)."""
    window: tuple
    end: float
    flights: list
    measured: list
    setup_s: float
    stats0: Dict[int, Dict]
    stats1: Dict[int, Dict]
    lanes: int
    chunk: int
    dims: object
    peaks: Dict
    trace: object = None
    steps: List[Step] = field(default_factory=list)
    emitted_traced: int = 0

    def stats_delta(self) -> Dict[str, float]:
        keys = self.stats1[next(iter(self.stats1))].keys()
        return {k: sum(self.stats1[i][k] - self.stats0[i][k]
                       for i in self.stats1) for k in keys}


class Probes:
    """Host spans and the per-dispatch work record of a traced run,
    wrapped on the objects the harness built (the program is not
    changed). Records only while ``on``."""

    def __init__(self, cluster):
        import jax
        self.on = False
        self.steps: List[Step] = []
        span = jax.profiler.TraceAnnotation

        def wrap(obj, attr, name, after=None):
            fn = getattr(obj, attr)

            def wrapped(*a, **kw):
                with span(name):
                    out = fn(*a, **kw)
                if after is not None and self.on:
                    after(a, out)
                return out
            setattr(obj, attr, wrapped)

        wrap(cluster, "submit", "bench.submit")
        wrap(cluster.gs, "schedule", "bench.schedule")
        for i, e in cluster.engines.items():
            wrap(e, "step", f"bench.engine_step.{i}")
            wrap(e.scheduler, "form_batch", f"bench.form_batch.{i}")
            wrap(e, "_lower_batch", f"bench.lower.{i}",
                 lambda a, _: self.steps.append(self.step_of(a[0])))

    @staticmethod
    def step_of(wb) -> Step:
        chunks = [(int(s), int(n)) for s, n in zip(wb.chunk_start,
                                                   wb.chunk_len) if n > 0]
        # a real lane's table row starts at one of its pages; padding
        # rows are all the reserved scratch page 0
        lens = [int(p) + 1 for p, row in zip(wb.dec_pos, wb.dec_page_table)
                if row[0] != 0]
        return Step(chunks, lens)


def start_profile() -> None:
    """Profile device and host (no Python tracer) into TRACE_DIR."""
    import jax
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)


def execute(cell: Cell, seed: int, seconds: float, trace: bool, *,
            devices, peaks: Dict, t_start: float = T_START,
            controls=(), inspect=None) -> Dict:
    """One run of ``cell``: the result line's object (without
    ``device``), plus the gaps (``gaps``) when ``controls`` are asked. ``inspect`` is
    called with the cluster once it is built (the sweep reads the
    programs' memory there)."""
    import jax
    import numpy as np
    from repro.core.request import Request, RequestState
    from repro.launch.serve import enable_compile_cache, engine_config
    from repro.serving.cluster import ClusterRuntime

    conf, cp = cell.config, cell.params
    dims = model.dims_of(conf)
    cache_dir = enable_compile_cache()
    compiles = [0]

    def on_event(event, duration, **_):
        if event in COMPILE_EVENTS:
            compiles[0] += 1
    jax.monitoring.register_event_duration_secs_listener(on_event)

    weights = model.make_weights(dims, seed, conf["model"]["torch_dtype"],
                                 device=devices[0])
    ecfg = engine_config(conf["max_context"], conf["pool_tokens"])
    cl = ClusterRuntime(model.program_config(conf), weights,
                        num_instances=cp["replicas"], engine_cfg=ecfg,
                        policy="e2")
    cl.warmup()
    if inspect is not None:
        inspect(cl)
    t_built = time.perf_counter() - t_start
    log(f"built {cp['replicas']} replica(s) of {conf['name']} in "
        f"{t_built:.2f} s (compile cache {cache_dir})")

    ramp, drain = cp["ramp_s"], cp["drain_s"]
    st = traffic.stream(cell.mix, rate=cp["rate_rps"],
                        blocks=(ramp, seconds, drain), vocab=dims.vocab,
                        max_context=conf["max_context"], seed=seed)
    probes = Probes(cl) if trace else None
    done_states = (RequestState.FINISHED, RequestState.FAILED)
    clock = harness.Clock()
    failed_fill = harness.fill(cl, st.fill, clock, request_cls=Request,
                               finished_states=done_states,
                               limit_s=FILL_LIMIT_S,
                               wave=ecfg.max_batch_requests * cp["replicas"])
    if trace:
        # before the ramp's clock starts, and stopped once the drain is
        # over: the profiler's start and its writing the trace stall
        # the host for seconds, which must fall on no request
        start_profile()
    r0 = clock()
    log(f"cache warm-up: {len(st.fill)} requests in {r0:.2f} s, "
        f"{failed_fill} failed")
    flights = [harness.Flight(p, r0 + float(d))
               for p, d in zip(st.timed, st.due)]
    window = (r0 + ramp, r0 + ramp + seconds)
    mark: Dict = {}

    def snapshot():
        return {i: dict(e.stats) for i, e in cl.engines.items()}

    def start():
        mark["setup_s"] = time.perf_counter() - t_start
        mark["stats0"], mark["compiles0"] = snapshot(), compiles[0]
        mark["tokens0"] = sum(len(f.token_times) for f in flights)
        if probes is not None:
            mark["span"] = jax.profiler.TraceAnnotation(WINDOW_SPAN)
            mark["span"].__enter__()
            probes.on = True

    def end():
        mark["stats1"], mark["compiles1"] = snapshot(), compiles[0]
        if probes is not None:
            probes.on = False
            mark["span"].__exit__(None, None, None)
            mark["emitted"] = (sum(len(f.token_times) for f in flights)
                               - mark["tokens0"])

    idle = None
    if probes is not None:
        def idle(s):
            with jax.profiler.TraceAnnotation("bench.sleep"):
                clock.sleep(s)
    try:
        with harness.HostWatch() as watch:
            loop = harness.drive(cl, flights, clock, window=window,
                                 drain_limit=window[1] + drain,
                                 request_cls=Request,
                                 finished_states=done_states,
                                 on_window_start=start,
                                 on_window_end=end, idle_wait=idle,
                                 watch=watch)
        t_end = clock()
    finally:
        if trace:
            jax.profiler.stop_trace()
    measured = [f for f in flights if window[0] <= f.due < window[1]]
    mem = [d.memory_stats() or {} for d in devices]
    peak = max(m.get("peak_bytes_in_use", 0) for m in mem)
    run = Run(window, t_end, flights, measured, mark["setup_s"],
              mark["stats0"], mark["stats1"], ecfg.max_batch_requests,
              ecfg.chunk_size, dims, peaks)
    if probes is not None:
        from bench import trace as tr
        run.steps = probes.steps
        run.emitted_traced = mark["emitted"]
        t0 = time.perf_counter()
        run.trace = tr.load(str(next(TRACE_DIR.rglob("*.xplane.pb"))))
        log(f"trace: {run.trace.window_s:.3f} s window, "
            f"{sum(len(c.ops) for c in run.trace.chips)} device ops on "
            f"{len(run.trace.chips)} chip(s), read in "
            f"{time.perf_counter() - t0:.2f} s")
    names = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for name in names:
        v = read_metric(name, run)
        if v is not None:
            metrics[name] = v
    unfinished = sum(f.done is None for f in measured)
    failed = sum(f.failed for f in measured) + unfinished
    short = sum(1 for f in measured if f.done is not None and not f.failed
                and len(f.request.output_tokens) != f.prompt.max_new_tokens)
    log(f"window {seconds} s: {len(measured)} measured requests "
        f"({unfinished} unfinished at the drain limit, {failed} failed), "
        f"{loop['steps']} steps, {loop['submitted']} submitted, "
        f"drain ended {t_end - window[1]:.2f} s after the window, "
        f"compiles in window {mark['compiles1'] - mark['compiles0']}")
    log(f"samples: {len(measured)} ttft/latency, "
        f"{sum(max(len(f.token_times) - 1, 0) for f in measured)} itl gaps")
    w = watch.longest or {}
    log(f"host: longest loop pass {w.get('wall_s', 0):.3f} s at "
        f"{w.get('at_s', 0) - window[0]:.2f} s into the window (main "
        f"thread cpu {w.get('cpu_s', 0):.3f} s, gc {w.get('gc_s', 0):.3f} s, "
        f"{w.get('involuntary_switches', 0)} involuntary switches); gc "
        f"over the loop {watch.gc_s:.3f} s, {watch.gc_full} full")
    log(f"device memory: {json.dumps(mem[0])}")

    # the program's state goes before the reference runs: the reference
    # then neither sets the memory peak nor sees the program's weights
    sampled = check.sample(measured, seed, cp["check"]["sample_tokens"])
    del cl, weights, probes
    gc.collect()
    t0 = time.perf_counter()
    ref_w = model.make_weights(dims, seed, conf["model"]["torch_dtype"],
                               device=devices[0])
    g = check.gaps(conf["reference"], dims, ref_w, sampled,
                   controls=controls)
    del ref_w
    widest = float(np.max(g["program"])) if len(g["program"]) else math.inf
    if not math.isfinite(widest):
        widest = math.inf
    lim = cp["check"]["limits"]
    checks = {"widest_gap": {"value": widest, "limit": lim["widest_gap"]},
              "unfinished": {"value": unfinished, "limit": 0},
              "failed": {"value": failed, "limit": 0},
              "short_outputs": {"value": short, "limit": 0}}
    log(f"reference: {len(sampled)} requests, {len(g['program'])} served "
        f"tokens, {int(np.sum(g['program'] == 0))} the reference's own "
        f"first choice, in {time.perf_counter() - t0:.2f} s")
    out = {"correct": check.verdict(checks), "attempted": len(measured),
           "failed": failed, "metrics": metrics,
           "memory_peak_bytes": peak, "checks": checks}
    if trace:
        from bench import trace as tr
        out["busy_s"] = tr.busy_s(run.trace)
        out["window_s"] = run.trace.window_s
        out["breakdown"] = tr.breakdown(run.trace)
    if controls:
        out["gaps"] = g
    return out


def result_line(out: Dict, cell: Cell, devices) -> Dict:
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    units = {m["name"]: m["unit"]
             for m in spec["end_to_end"] + spec["per_layer"]}
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": out["memory_peak_bytes"]}
    if "busy_s" in out:
        dev["busy_s"], dev["window_s"] = out["busy_s"], out["window_s"]
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"],
            "metrics": {k: {"value": v, "unit": units[k]}
                        for k, v in out["metrics"].items()},
            "device": dev}
    if "breakdown" in out:
        line["breakdown"] = out["breakdown"]
    line["checks"] = out["checks"]
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    devices = find_chips(cell.chips)
    peaks = load_peaks(devices[0].device_kind)
    out = execute(cell, args.seed, args.seconds, bool(args.trace),
                  devices=devices, peaks=peaks)
    line = result_line(out, cell, devices)
    for k, c in out["checks"].items():
        log(f"check {k}: {c['value']} (limit {c['limit']})")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
