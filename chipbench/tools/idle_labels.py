#!/usr/bin/env python3
"""One run of a cell exactly as ``run.py`` makes it, whose ``breakdown``
labels each idle gap of the device by the innermost host span that
covered it (``chipbench/idle.py``), the program's ``engine.*`` and
``runtime.*`` spans included, instead of by ``bench.step`` alone.

    python3 chipbench/tools/idle_labels.py --workload <cell> --seed <n> \\
        --seconds <s> --trace 1

The arguments and the result line are ``run.py``'s.  Every label, the
labels by the program running at each gap's end with the number and the
longest of their gaps and the seconds of those inside one program,
``run.py``'s own labels and the seconds taken to read the trace go to
standard error as one JSON line that starts ``idle labels:``.
"""
from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import idle as I  # noqa: E402
from chipbench import run as RUN  # noqa: E402
from chipbench import trace as T  # noqa: E402


def labelled_read(path: str, found: dict) -> dict:
    """``trace.read`` with the idle gaps labelled by the innermost span;
    what else was found goes into ``found``."""
    from jax.profiler import ProfileData
    t = time.perf_counter()
    data = ProfileData.from_file(path)
    red = T.reduce(data.planes)
    found["read_s"] = time.perf_counter() - t
    found["trace_bytes"] = os.path.getsize(path)
    found["bench_labels"] = red["idle"]
    red["idle"] = found["labels"] = I.label(data.planes)
    by: dict = {}
    for text, sec, inside in I.gaps(data.planes, holding=True):
        n, total, top, within = by.get(text, (0, 0.0, 0.0, 0.0))
        by[text] = (n + 1, total + sec, max(top, sec),
                    within + (sec if inside else 0.0))
    found["labels_holding"] = {
        k: {"s": v[1], "n": v[0], "max_s": v[2], "inside_s": v[3]}
        for k, v in by.items()}
    found["labelled_s"] = time.perf_counter() - t
    return red


def main(argv=None) -> int:
    found: dict = {}
    T.read = lambda path: labelled_read(path, found)
    rc = RUN.main(argv)
    print("idle labels: " + json.dumps(found), file=sys.stderr, flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
