"""Mean time of the engine's decode steps inside the window less their
wait for the next tokens (``engine.decode`` minus its
``engine.decode.sync``): dispatch and bookkeeping on the host."""
from chipbench.metrics._spans import host_ms


def read(run):
    return host_ms(run, "engine.decode", "engine.decode.sync")
