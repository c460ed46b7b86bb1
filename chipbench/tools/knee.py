#!/usr/bin/env python3
"""Find a configuration's knee once, on the chip: serve a traffic mix at
several offered rates from one engine and report, for each, the backlog
(requests scheduled but not finished) at the window's start and end.  The
knee is the highest rate whose backlog does not grow through the window.

    python3 chipbench/tools/knee.py --workload phi3-chat --rates 0.3,0.4,0.5 --seconds 40

Writes one JSON line per rate to standard output, then the knee: the
service capacity read at the most loaded point, where the backlog grows,
as output tokens received over the window divided by the schedule's mean
output tokens per request (a finer reading than the count of requests
that happen to finish inside the window).  ``--set name=factor,...``
writes ``factor x knee`` (two significant digits) as the rate of each
named traffic file.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(HERE), os.path.join(os.path.dirname(HERE), "src")]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=2 ** 31 + 101)
    ap.add_argument("--set", default="")
    args = ap.parse_args()
    from chipbench import run as RUN
    from chipbench import serve as S
    from chipbench import traffic as TR
    from repro.util import enable_compile_cache
    RUN.device_check(1)
    enable_compile_cache()
    cell = RUN.load_cell(args.workload)
    eng, bucket_of = S.build(cell.config, cell.traffic, seed=args.seed,
                             ref=cell.ref)
    rates = [float(r) for r in args.rates.split(",")]
    rows = []
    horizon = cell.traffic["ramp_s"] + args.seconds + 1.0
    warmed = set()
    for r in rates:
        warmed.update(S.warm_buckets(
            eng, TR.schedule(dict(cell.traffic, rate_per_s=r), horizon),
            bucket_of))
    for r in rates:
        S.reset(eng)
        rec, compiles = S.serve_window(eng, cell.traffic, seed=args.seed,
                                       seconds=args.seconds, warmed=warmed,
                                       rate=r)
        w0, w1 = rec.window
        run = RUN.Run(seconds=args.seconds, window=(w0, w1), t0=rec.t0,
                      arrivals=rec.arrivals, reqs=rec.reqs, steps=rec.steps,
                      setup_s=0.0, model={}, peak={})

        def backlog(t):
            due = sum(rec.t0 + a.at <= t for a in rec.arrivals)
            return int(due) - sum(q.done is not None and q.done <= t
                             for q in rec.reqs.values())
        dec = [s for s in rec.steps if s.kind == "decode"
               and w0 <= s.start and s.end <= w1]
        row = {"workload": args.workload, "rate": r,
               "backlog_start": backlog(w0), "backlog_end": backlog(w1),
               "occupancy": (sum(len(s.lengths) for s in dec) / len(dec)
                             if dec else 0.0),
               "compiles_in_window": compiles,
               "finished_per_s": sum(w0 < q.done <= w1 for q in rec.reqs.values()
                                     if q.done is not None) / args.seconds}
        for name in ("output_tok_s", "ttft_p90_ms", "itl_p95_ms",
                     "decode_step_ms.chat", "queue_wait_p90_ms"):
            row[name] = RUN.reader(name)(run)
        print(json.dumps(row, default=float), flush=True)
        rows.append(row)
    sched = TR.schedule(cell.traffic, horizon)
    mean_out = sum(a.new_tokens for a in sched) / len(sched)
    knee = rows[-1]["output_tok_s"] / mean_out
    print(json.dumps({"workload": args.workload, "knee": knee,
                      "mean_output_tokens": mean_out}), flush=True)
    for item in filter(None, args.set.split(",")):
        name, factor = item.split("=")
        path = os.path.join(HERE, "traffic", name + ".json")
        t = RUN.load_json(path)
        t["rate_per_s"] = float(f"{float(factor) * knee:.2g}")
        t["rate_basis"] = (f"{factor} x the {args.workload} knee of "
                           f"{knee:.3g} req/s that chipbench/tools/knee.py "
                           f"read on the chip at rates {args.rates} "
                           f"(PERF.md section 4)")
        with open(path, "w") as f:
            json.dump(t, f, indent=2)
            f.write("\n")
        print(json.dumps({"traffic": name, "rate_per_s": t["rate_per_s"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
