"""Bucket tokens minus prompt tokens, over bucket tokens, of the prefill
steps that started inside the window, in percent."""
from chipbench.metrics._common import in_window


def read(run):
    steps = [s for s in run.steps
             if s.kind == "prefill" and in_window(run, s.start)]
    total = sum(s.bucket for s in steps)
    if not total:
        return None
    return 100.0 * (total - sum(s.lengths[0] for s in steps)) / total
