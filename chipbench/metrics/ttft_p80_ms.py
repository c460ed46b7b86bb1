"""80th percentile of time to first token, from the scheduled arrival, over
every request scheduled inside the window; one without a first token by
the window's end counts at its age then."""
from chipbench.metrics._common import ttft_pct


def read(run):
    return ttft_pct(run, 80)
