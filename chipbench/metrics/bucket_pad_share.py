"""Bucket tokens less prompt tokens, over bucket tokens, of the admits
that started inside the window, as the engine counted them
(``engine.admit`` ``bucket`` and ``tokens``), in percent."""
from chipbench.metrics._spans import records


def read(run):
    w0, w1 = run.window
    admits = [r.attrs for r in records(run)
              if r.name == "engine.admit" and w0 <= r.start <= w1]
    total = sum(a["bucket"] for a in admits)
    if not total:
        return None
    return 100.0 * (total - sum(a["tokens"] for a in admits)) / total
