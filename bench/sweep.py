#!/usr/bin/env python3
"""Find a cell's knee: run it at a list of fixed arrival rates, one
after another in this process, and print rate against the end-to-end
metrics. The benchmark's own runs never sweep; the rate each cell runs
at (``rate_rps`` in ``bench/cells/<cell>.json``) was chosen from one
sweep on the chip, recorded in PERF.md.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 0.5,1,1.5,2 [--drain <s>]
"""

from __future__ import annotations

import argparse
import copy
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run  # noqa: E402


def memory_report(cl) -> None:
    """The compiled step programs' memory, from the compiler's own
    analysis, and whether the Pallas kernel is in the mixed step."""
    e = next(iter(cl.engines.values()))
    for name, lo in zip(("mixed", "decode"),
                        e._lowered(e._padding_batch())[:2]):
        c = lo.compile()
        m = c.memory_analysis()
        run.log(f"{name} step: arguments {m.argument_size_in_bytes}, "
                f"outputs {m.output_size_in_bytes}, temp "
                f"{m.temp_size_in_bytes}, aliased "
                f"{m.alias_size_in_bytes} bytes; pallas kernel "
                f"{'tpu_custom_call' in c.as_text()}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--drain", type=float, default=None)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    devices = run.find_chips(cell.chips)
    peaks = run.load_peaks(devices[0].device_kind)
    rows = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        c = copy.deepcopy(cell)
        c.params["rate_rps"] = rate
        if args.drain is not None:
            c.params["drain_s"] = args.drain
        t0 = time.perf_counter()
        out = run.execute(c, args.seed + i, args.seconds, False,
                          devices=devices, peaks=peaks, t_start=t0,
                          inspect=memory_report if i == 0 else None)
        row = {"rate_rps": rate, "attempted": out["attempted"],
               "failed": out["failed"], "correct": out["correct"],
               "widest_gap": out["checks"]["widest_gap"]["value"],
               **out["metrics"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
    keys = ["rate_rps", *cell.end_to_end, "attempted", "failed",
            "correct"]
    print(" | ".join(keys))
    for r in rows:
        print(" | ".join(f"{r.get(k)}" if not isinstance(r.get(k), float)
                         else f"{r[k]:.4g}" for k in keys))
    return 0


if __name__ == "__main__":
    sys.exit(main())
