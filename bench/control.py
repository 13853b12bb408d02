#!/usr/bin/env python3
"""Read both ends of a cell's correctness limit on the chip: for each
seed, one run of the cell (its own load and sizes) whose sampled
served tokens are scored against the float32 reference (the program's
reading) and whose positions are also scored for the first choice of
the reference in fp8 and in int8 (the controls' readings). Each
control's widest gap goes through the same verdict as the program's,
against the cell's limit, and must come out not correct. All seeds in
this process.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

Prints one JSON line per seed; the benchmark's runs never compute the
control.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import check, run  # noqa: E402

CONTROLS = ("fp8", "int8")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    devices = run.find_chips(cell.chips)
    peaks = run.load_peaks(devices[0].device_kind)
    limit = cell.params["check"]["limits"]["widest_gap"]
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run.execute(cell, seed, args.seconds, False, devices=devices,
                          peaks=peaks, t_start=time.perf_counter(),
                          controls=CONTROLS)
        row = {"seed": seed, "tokens": int(len(out["gaps"]["program"])),
               "correct": out["correct"], "attempted": out["attempted"],
               "failed": out["failed"]}
        for k, g in out["gaps"].items():
            row[f"{k}_widest"] = float(np.max(g))
            row[f"{k}_mean"] = float(np.mean(g))
            row[f"{k}_off"] = int(np.sum(g > 0))
            if k != "program":
                row[f"{k}_correct"] = check.verdict(
                    {"widest_gap": {"value": float(np.max(g)),
                                    "limit": limit}})
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
