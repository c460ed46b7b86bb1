"""Output tokens the host received inside the window, over the window."""
from chipbench.metrics._common import in_window


def read(run):
    n = sum(in_window(run, t) for r in run.reqs.values()
            for t in r.token_times)
    return n / run.seconds
