"""Reduce a profiler trace (``.xplane.pb``) to the numbers the metric
readers take.

On a TPU the device plane ``/device:TPU:<n>`` holds a line "XLA Modules"
(one event per executed program, named ``jit_<fn>(<hash>)``) and a line
"XLA Ops" (one event per HLO instruction, named by the instruction's
text, ``%<op>.<k> = ...``).  A Pallas kernel is an op whose text holds
``custom_call_target="tpu_custom_call"``, named after the function that
called ``pallas_call``.  Host spans recorded with ``TraceAnnotation``
sit on the host plane's lines, on the same clock.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict

_OP = re.compile(r"%([A-Za-z0-9_\-]+?)(?:\.\d+)?\s=")
#: ops that hold other ops: their time is their children's
_CONTAINERS = ("while", "conditional", "call")
_MODULE = re.compile(r"^(.*?)\(\d+\)$")
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'


def op_name(text: str) -> str:
    """``%decode_attention.6 = bf16[...] custom-call(...)`` ->
    ``decode_attention``."""
    m = _OP.match(text)
    return m.group(1) if m else text


def module_name(text: str) -> str:
    """``jit__decode_impl(1386...)`` -> ``jit__decode_impl``."""
    m = _MODULE.match(text)
    return m.group(1) if m else text


def _union(intervals: list) -> list:
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def reduce(planes, host_prefix: str = "bench.") -> dict:
    """``planes``: an iterable of objects with ``name`` and ``lines``
    (``jax.profiler.ProfileData(...).planes``).

    -> {"chips", "busy_s" (mean over chips),
        "modules": {name: seconds}, "kernels": {name: seconds},
        "ops": {name: seconds}, "idle": {"<host activity> before <next
        program>": seconds} over the first chip's gaps}"""
    chips, host = [], []
    for pl in planes:
        lines = {ln.name: list(ln.events) for ln in pl.lines}
        if pl.name.startswith("/device:TPU"):
            chips.append(lines)
        elif pl.name.startswith("/host"):
            for evs in lines.values():
                host += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                         for e in evs if e.name.startswith(host_prefix)]
    modules: dict = defaultdict(float)
    kernels: dict = defaultdict(float)
    ops: dict = defaultdict(float)
    busy, gaps0, starts0 = [], [], []
    for i, lines in enumerate(chips):
        for e in lines.get("XLA Modules", []):
            modules[module_name(e.name)] += e.duration_ns * 1e-9
            if i == 0:
                starts0.append((e.start_ns, module_name(e.name)))
        intervals = []
        for e in lines.get("XLA Ops", []):
            name = op_name(e.name)
            d = e.duration_ns * 1e-9
            if name not in _CONTAINERS:
                ops[name] += d
            if KERNEL_MARK in e.name:
                kernels[name] += d
            intervals.append((e.start_ns, e.start_ns + e.duration_ns))
        merged = _union(intervals)
        busy.append(sum(b - a for a, b in merged) * 1e-9)
        if i == 0:
            gaps0 = [(merged[k][1], merged[k + 1][0])
                     for k in range(len(merged) - 1)]
    idle: dict = defaultdict(float)
    host.sort()
    starts0.sort()
    host_starts = [s for s, _, _ in host]
    module_starts = [s for s, _ in starts0]
    for a, b in gaps0:
        # host spans follow one another: the last to start before the
        # gap's middle is the only one that can hold it
        mid = 0.5 * (a + b)
        k = bisect.bisect_right(host_starts, mid) - 1
        label = host[k][2] if k >= 0 and host[k][1] >= mid \
            else "outside engine steps"
        k = bisect.bisect_left(module_starts, b)
        if k < len(starts0):
            label += " before " + starts0[k][1]
        idle[label] += (b - a) * 1e-9
    n = max(len(chips), 1)
    return {"chips": len(chips), "busy_s": sum(busy) / n,
            "modules": dict(modules), "kernels": dict(kernels),
            "ops": dict(ops), "idle": dict(idle)}


def read(path: str) -> dict:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(path).planes)


def breakdown(red: dict, n: int = 10) -> dict:
    """The contract's ``breakdown``: the device ops that took most time
    and the idle time by what the host was doing."""
    top = sorted(red["ops"].items(), key=lambda kv: -kv[1])[:n]
    idle = sorted(red["idle"].items(), key=lambda kv: -kv[1])[:n]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in idle]}
