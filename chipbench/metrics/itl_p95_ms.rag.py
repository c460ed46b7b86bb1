"""95th percentile of every gap between consecutive output tokens of every
request, both tokens inside the window, in the RAG cell: a gap there
often holds another request's prefill, so its runs spread several times
wider than a chat cell's, and it has a bound of its own."""
from chipbench.metrics._common import itl_pct


def read(run):
    return itl_pct(run, 95)
