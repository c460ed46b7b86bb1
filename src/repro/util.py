"""Small shared utilities."""
import os


def cost_mode() -> bool:
    """Dry-run cost lowering: unroll scans so HLO FLOPs reflect true trip
    counts (XLA cost analysis counts while-loop bodies once)."""
    return os.environ.get("REPRO_COST_MODE", "0") == "1"


def opt_flags() -> set:
    """Named perf optimizations for §Perf experiments (REPRO_OPTS=a,b,c)."""
    v = os.environ.get("REPRO_OPTS", "")
    return {x.strip() for x in v.split(",") if x.strip()}


#: the checkout's fixed compile-cache directory (gitignored); a fixed path
#: is part of what lets a later process find the entries again
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as is (JAX reads
    it itself); otherwise the cache lives at ``COMPILE_CACHE_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR
