"""Mean host time of the engine's decode steps (``engine.decode`` spans)
that began and ended inside the window."""
from chipbench.metrics._spans import inside, mean_ms


def read(run):
    return mean_ms(inside(run, "engine.decode"))
