#!/usr/bin/env python3
"""Readings for a cell's correctness limit, on the chip, in one process:
for each seed, a run of the cell at its own load and sizes with a short
window, the program's widest logit gap, and the float8 control's widest
gap over the same requests and positions.  The limit lies between the
program's largest reading and the control's smallest.

    python3 chipbench/tools/readings.py --workload phi3-chat --seeds 11,12,13 --seconds 20

Writes one JSON line per seed to standard output.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(HERE), os.path.join(os.path.dirname(HERE), "src")]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args()
    from chipbench import run as RUN
    from repro.util import enable_compile_cache
    device = RUN.device_check(1)
    enable_compile_cache()
    peak = RUN.load_json(os.path.join(HERE, "peaks.json"))[device["kind"]]
    cell = RUN.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = RUN.measure(cell, seed=seed, seconds=args.seconds, trace=False,
                          peak=peak, control=True,
                          t_start=time.perf_counter())
        print(json.dumps({"workload": args.workload, "seed": seed,
                          **out["gaps"], "check_s": out["check_s"],
                          "finished": out["finished"],
                          "memory_peak_bytes": out["memory_peak_bytes"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
