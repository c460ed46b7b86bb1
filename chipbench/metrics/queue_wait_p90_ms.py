"""90th percentile of the time from scheduled arrival to the start of the
request's prefill step, over requests scheduled inside the window."""
from chipbench.metrics._common import arrivals_in_window, censored, pct


def read(run):
    return pct([1e3 * (censored(run, r.prefill_start if r else None)
                       - (run.t0 + a.at))
                for a, r in arrivals_in_window(run)], 90)
