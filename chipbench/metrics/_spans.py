"""Helpers for the readers of the program's own host spans
(``repro.core.spans``), which it keeps while a profiler session runs, as
in a traced run.  The spans share the window's clock, ``time.perf_counter``.
On a program without them every reader returns None."""
from __future__ import annotations

from collections import defaultdict


def records(run) -> list:
    """Every span the program kept; [] where it keeps none."""
    try:
        from repro.core import spans
    except ImportError:
        return []
    return spans.snapshot()


def inside(run, name: str) -> list:
    """Spans ``name`` that began and ended inside the window."""
    w0, w1 = run.window
    return [r for r in records(run)
            if r.name == name and w0 <= r.start and r.end <= w1]


def mean_ms(spans) -> float | None:
    if not spans:
        return None
    return 1e3 * sum(r.end - r.start for r in spans) / len(spans)


def host_ms(run, name: str, sync: str) -> float | None:
    """Mean time of the spans ``name`` inside the window less their child
    ``sync`` (the wait for the device): the host's own time."""
    parents = inside(run, name)
    if not parents:
        return None
    waited: dict = defaultdict(float)
    ids = {r.id for r in parents}
    for r in records(run):
        if r.name == sync and r.parent in ids:
            waited[r.parent] += r.end - r.start
    return 1e3 * sum(r.end - r.start - waited[r.id]
                     for r in parents) / len(parents)
