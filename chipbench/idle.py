"""The device's idle gaps, each labelled by the innermost host span that
covered it.

The program's own spans (``engine.*``, ``runtime.*``; see
``repro.core.spans``) nest inside the benchmark's ``bench.step``.  A gap
of the first chip is labelled ``"<span> before <program>"``: the
innermost of those spans open at the gap's middle (the one that began
last), and the first program to start at or after the gap's end; with no
span open, ``outside engine steps``.  On a trace whose only spans are
``bench.`` ones this is ``trace.reduce``'s ``idle``, float for float.
"""
from __future__ import annotations

import bisect
from collections import defaultdict

from chipbench import trace as T

PREFIXES = ("bench.", "engine.", "runtime.")


def label(planes, prefixes=PREFIXES, holding: bool = False) -> dict:
    """``planes`` as for ``trace.reduce`` -> {label: seconds}.  With
    ``holding``, the program named is the one running at the gap's end,
    whose next op the gap delayed (a program's event can begin before its
    first op, so the first to start after the gap may be the one after)."""
    idle: dict = defaultdict(float)
    for text, seconds, _ in gaps(planes, prefixes, holding):
        idle[text] += seconds
    return dict(idle)


def gaps(planes, prefixes=PREFIXES, holding: bool = False) -> list:
    """Each idle gap of the first chip, in time order, as (label, seconds,
    inside): the labels are ``label``'s; ``inside`` is true where one
    program's event holds the whole gap, a pause between two of its ops
    rather than between programs."""
    chip, host = None, []
    for pl in planes:
        lines = {ln.name: list(ln.events) for ln in pl.lines}
        if pl.name.startswith("/device:TPU") and chip is None:
            chip = lines
        elif pl.name.startswith("/host"):
            for evs in lines.values():
                host += [(e.start_ns, -e.duration_ns, e.name) for e in evs
                         if e.name.startswith(prefixes)]
    if chip is None:
        return []
    merged = T._union([(e.start_ns, e.start_ns + e.duration_ns)
                       for e in chip.get("XLA Ops", [])])
    starts = sorted((e.start_ns, T.module_name(e.name),
                     e.start_ns + e.duration_ns)
                    for e in chip.get("XLA Modules", []))
    module_starts = [s for s, _, _ in starts]
    # of spans starting together, the longer is pushed first: the one
    # left on top is the inner
    host.sort()
    out: list = []
    stack: list = []
    k = 0
    for (_, a), (b, _) in zip(merged, merged[1:]):
        mid = 0.5 * (a + b)
        while k < len(host) and host[k][0] <= mid:
            s, neg, name = host[k]
            stack.append((s - neg, name))
            k += 1
        # a span that ended before the middle cannot hold it, nor can it
        # hold any later gap
        while stack and stack[-1][0] < mid:
            stack.pop()
        text = stack[-1][1] if stack else "outside engine steps"
        j = bisect.bisect_left(module_starts, b)
        if holding and j > 0 and starts[j - 1][2] >= b:
            j -= 1
        if j < len(starts):
            text += " before " + starts[j][1]
        m = bisect.bisect_right(module_starts, a) - 1
        out.append((text, (b - a) * 1e-9, m >= 0 and starts[m][2] >= b))
    return out
