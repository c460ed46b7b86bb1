"""90th percentile of how late the runtime's loop submitted each request
after its due instant (``runtime.submit`` ``late_s``), over requests due
inside the window."""
from chipbench.metrics._common import pct
from chipbench.metrics._spans import records


def read(run):
    w0, w1 = run.window
    return pct([1e3 * r.attrs["late_s"] for r in records(run)
                if r.name == "runtime.submit"
                and w0 <= r.start - r.attrs["late_s"] <= w1], 90)
