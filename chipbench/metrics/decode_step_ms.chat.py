"""Host time of the decode steps that began and ended inside the window,
over their number."""
from chipbench.metrics._common import decode_step_ms


def read(run):
    return decode_step_ms(run)
