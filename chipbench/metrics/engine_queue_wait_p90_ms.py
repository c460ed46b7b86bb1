"""90th percentile of each request's wait in the engine's queue, from the
engine's submit to the start of its admit (``engine.queue``), over the
requests the runtime submitted inside the window (``runtime.submit``);
one not admitted by the window's end counts at its wait then.  Request
ids restart with each run, so only marks of this run (begun at or after
its start) are matched."""
from chipbench.metrics._common import pct
from chipbench.metrics._spans import records


def read(run):
    w0, w1 = run.window
    recs = records(run)
    queued = {r.attrs["req"]: r for r in recs
              if r.name == "engine.queue" and r.start >= run.t0}
    waits = []
    for s in recs:
        if s.name != "runtime.submit" or not w0 <= s.start <= w1:
            continue
        q = queued.get(s.attrs["req"])
        if q is not None and q.end <= w1:
            waits.append(q.end - q.start)
        else:
            waits.append(w1 - (q.start if q is not None else s.start))
    return pct([1e3 * w for w in waits], 90)
