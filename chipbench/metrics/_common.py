"""Helpers the metric readers share.  Every reader is a file
``metrics/<metric name>.py`` with ``read(run) -> float | None``; ``run``
is a ``chipbench.run.Run``.  A reader that finds nothing to read returns
None and the metric is left out of the result line."""
from __future__ import annotations

import numpy as np

from chipbench import counts as C


def pct(values, q):
    return float(np.percentile(np.asarray(values, float), q)) if values else None


def in_window(run, t):
    w0, w1 = run.window
    return w0 <= t <= w1


def arrivals_in_window(run):
    """Every request scheduled inside the window, submitted or not, with
    its record (None if the runtime had not submitted it by the end)."""
    w0, w1 = run.window
    return [(a, run.reqs.get(a.index)) for a in run.arrivals
            if w0 <= run.t0 + a.at <= w1]


def censored(run, t):
    """A time that has not come by the window's end counts at the end."""
    return t if t is not None and t <= run.window[1] else run.window[1]


def decode_step_ms(run):
    steps = [s for s in run.steps if s.kind == "decode"
             and in_window(run, s.start) and in_window(run, s.end)]
    if not steps:
        return None
    return 1e3 * sum(s.end - s.start for s in steps) / len(steps)


def traced(run, kind):
    return [s for s in run.steps if s.kind == kind and s.traced]


def mfu(run, kind, module, flops_of):
    """Model FLOPs of the traced steps of ``kind`` over the device time of
    their program times the chip's peak, in percent."""
    if run.trace is None:
        return None
    steps = traced(run, kind)
    t = run.trace["modules"].get(module)
    if not steps or not t:
        return None
    flops = sum(flops_of(run.model, s.lengths) for s in steps)
    return 100.0 * flops / (t * run.peak["bf16_flops_per_s"])


def roofline(run, kind, kernel, counts_of):
    """Least time the chip could take for the kernel's calls in the traced
    steps, over the kernel's device time, in percent."""
    if run.trace is None:
        return None
    steps = traced(run, kind)
    t = run.trace["kernels"].get(kernel)
    if not steps or not t:
        return None
    least = 0.0
    for s in steps:
        f, b = counts_of(run.model, s.lengths)
        least += C.roofline_seconds(f, b, run.peak)[0]
    return 100.0 * least / t


def ttft_pct(run, q):
    """Time to first token from the scheduled arrival, at percentile ``q``,
    over every request scheduled inside the window."""
    out = []
    for a, r in arrivals_in_window(run):
        first = r.token_times[0] if r is not None and r.token_times else None
        out.append(1e3 * (censored(run, first) - (run.t0 + a.at)))
    return pct(out, q)


def itl_pct(run, q):
    """Gaps between consecutive output tokens, both inside the window, at
    percentile ``q``."""
    return pct([1e3 * (b - a) for r in run.reqs.values()
                for a, b in zip(r.token_times, r.token_times[1:])
                if in_window(run, a) and in_window(run, b)], q)
