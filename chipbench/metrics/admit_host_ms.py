"""Mean time of the engine's admits inside the window less their wait for
the first token (``engine.admit`` minus its ``engine.prefill.sync``):
padding, prefill dispatch and the cache insert on the host."""
from chipbench.metrics._spans import host_ms


def read(run):
    return host_ms(run, "engine.admit", "engine.prefill.sync")
