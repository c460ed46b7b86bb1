"""The plain reference against the program's own forward on the CPU, at
the -smoke sizes of both configurations, with the served tree cast to
float32 so that only the arithmetic's order separates the two.  Each
configuration's reference is the module its file names."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import run as RUN
from chipbench import weights as W
from rehearse import smoke_file
from repro.configs.base import get_config
from repro.models import registry as R

SEED = 2 ** 31 + 99


def config_of(arch: str) -> dict:
    """The benchmark's configuration file that the program's ``arch``
    serves."""
    bench = RUN.load_json(os.path.join(RUN.ROOT, "BENCHMARK.json"))
    files = [RUN.load_json(os.path.join(RUN.ROOT, c["file"]))
             for c in bench["configs"]]
    return next(f for f in files if f["program_arch"] == arch)


@pytest.mark.parametrize("arch", ["phi3-mini-3.8b", "stablelm-3b"])
def test_reference_matches_program_forward(arch):
    config = config_of(arch)
    ref = RUN.reference(config["reference"])
    cfg = get_config(arch + "-smoke")
    params = W.make_tree(R.abstract_params(cfg), SEED, rules=ref.leaf_rules)
    params32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, (2, 40)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want = R.lm_logits(cfg, params32, {"tokens": jnp.asarray(tokens)},
                           impl="ref", remat=False)
    rows = np.tile(np.arange(40), (2, 1))
    got = ref.logits_at(smoke_file(config), SEED, tokens, rows)
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    assert err < 1e-4, err


def test_control_departs_from_reference():
    config = config_of("phi3-mini-3.8b")
    ref = RUN.reference(config["reference"])
    f = smoke_file(config)
    tokens = np.random.default_rng(1).integers(0, 256, (1, 32)).astype(np.int32)
    rows = np.arange(32)[None]
    a = ref.logits_at(f, SEED, tokens, rows)
    b = ref.logits_at(f, SEED, tokens, rows, control=True)
    rel = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(a))
    assert 1e-3 < rel < 0.5, rel
