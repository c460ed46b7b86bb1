"""95th percentile of every gap between consecutive output tokens of every
request, both tokens inside the window."""
from chipbench.metrics._common import itl_pct


def read(run):
    return itl_pct(run, 95)
