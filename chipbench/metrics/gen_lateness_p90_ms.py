"""90th percentile of how late the runtime submitted each request after its
scheduled arrival, over requests scheduled inside the window."""
from chipbench.metrics._common import arrivals_in_window, censored, pct


def read(run):
    return pct([1e3 * (censored(run, r.submit if r else None)
                       - (run.t0 + a.at))
                for a, r in arrivals_in_window(run)], 90)
