"""Weight rules by path.  A configuration's reference declares the rules
of its own leaves; what it does not declare keeps ``weights.init_rule``'s
own rules bit for bit.  The checksums below were taken from the weights
as made before references were named by configuration (the dense rules
alone), so a change to how phi3's or stablelm's leaves are made fails
here."""
import hashlib
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import run as RUN
from chipbench import weights as W
from chipbench.metrics._common import mfu
from chipbench.serve import Step
from repro.configs.base import get_config
from repro.models import registry as R

SEED = 2 ** 31 + 99
DATA = os.path.join(os.path.dirname(__file__), "data")

SMOKE_TREES = {
    "phi3-mini-3.8b":
        "b77c1d3db9485cd127786b50b9fe0eedb65e36f24c1b2b4b939efb405d2ec6cc",
    "stablelm-3b":
        "0482c69c5e6fbfe1508b1999c367790bd637a856d600b0dd0877d7733600ca66",
}
#: (leaf, one layer's shape at the published widths, layer) -> checksum
FULL_LEAVES = {
    ("groups/pos0/attn/q", (3072, 32, 96), 3):
        "dfd6e6903cb9af3b7aa36e5fc88c17d06b01b3d5bce8e6244e934dda19895be9",
    ("groups/pos0/mlp/wi_0", (3072, 8192), 3):
        "f167b9661df00bb928f396c5c9f7638aa112871942f325e23085bbebac0ff901",
    ("unembed/kernel", (32064, 3072), None):
        "daca1413b1e7b70a32795df3cca85c48db0887feb041a977982f6a765e8e91bf",
    ("groups/pos0/attn/q", (2560, 32, 80), 3):
        "6815aba5a6473602e450520d276fa3ebe8f2289af46ed7c624ac90754b5d31d8",
    ("groups/pos0/mlp/wi_0", (2560, 6912), 3):
        "d0b9666d183fc2f71ffacdb03d3342b3d48ad3d6b5a82ac9b24fd19954577818",
    ("unembed/kernel", (50304, 2560), None):
        "2578191ab873a8dc7f3a929d052b15c301cd83a0970b8b7378633b5803d0190a",
}


def digest(tree) -> str:
    h = hashlib.sha256()
    for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        h.update("/".join(str(getattr(k, "key", k)) for k in p).encode())
        a = np.asarray(x)
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def dense():
    name = RUN.load_json(os.path.join(
        RUN.ROOT, "chipbench/configs/phi3-mini-3.8b.json"))["reference"]
    return RUN.reference(name)


@pytest.mark.parametrize("arch", sorted(SMOKE_TREES))
def test_smoke_tree_keeps_its_bits(arch):
    tree = W.make_tree(R.abstract_params(get_config(arch + "-smoke")), SEED,
                       rules=dense().leaf_rules)
    assert digest(tree) == SMOKE_TREES[arch]


@pytest.mark.parametrize("leaf", sorted(FULL_LEAVES, key=str),
                         ids=lambda k: f"{k[0]}{list(k[1])}")
def test_full_width_leaf_keeps_its_bits(leaf):
    path, shape, layer = leaf
    made = jax.jit(lambda key: W.make_leaf(
        key, path, shape, jnp.bfloat16,
        None if layer is None else np.uint32(layer),
        dense().leaf_rules))(W.seed_key(SEED))
    assert digest({"leaf": made}) == FULL_LEAVES[leaf]


def moe_tree(rules):
    return W.make_tree(R.abstract_params(get_config("deepseek-moe-16b-smoke")),
                       SEED, rules=rules)


def exponent(leaf) -> int:
    """e of a leaf made of odd integers in [-255, 255] times 2**e: enough
    values that +-255 is among them."""
    return round(math.log2(float(jnp.max(jnp.abs(leaf.astype(jnp.float32))))
                           / 255))


def test_declared_rules_make_an_moe_tree():
    fixture = RUN.reference("moe_rules", DATA)
    tree = moe_tree(fixture.leaf_rules)
    moe = tree["groups"]["pos0"]["moe"]
    d = get_config("deepseek-moe-16b-smoke").d_model
    # odd integers have an RMS of 147.8: fan-in 64 -> 2**-10, where the
    # experts' axis (4) as fan-in would have given 2**-8
    want = round(math.log2(1 / math.sqrt(d) / W._ODD_RMS))
    assert want == -10 and moe["wi_0"].shape[1:] == (4, d, 64)
    for bank in ("wi_0", "wi_1", "wo", "router"):
        assert exponent(moe[bank]) == want, bank
    # the undeclared leaves are the dense rules' own: the shared experts'
    # 2-D matrices by their axis 0, the attention as phi3's
    assert exponent(moe["shared"]["wi_0"]) == want
    dense_tree = W.make_tree(
        R.abstract_params(get_config("phi3-mini-3.8b-smoke")), SEED)
    assert digest(tree["groups"]["pos0"]["attn"]) == \
        digest(dense_tree["groups"]["pos0"]["attn"])


@pytest.mark.parametrize("rules,leaf", [
    (None, "groups/pos0/moe/router"),
    ({"moe/router": ("matrix", (0,))}, "groups/pos0/moe/wi_0"),
    ({"router": ("matrix", (0,)), "shared/wo": ("matrix", (0,))},
     "groups/pos0/moe/wi_0"),
], ids=["no-rules", "router-only", "no-bank-rules"])
def test_moe_tree_without_its_rules_is_refused(rules, leaf):
    with pytest.raises(ValueError, match=leaf):
        moe_tree(rules)


def test_longest_declared_suffix_wins_at_a_slash():
    rules = {"wo": ("matrix", (0,)), "moe/wo": ("matrix", (0, 1))}
    assert W.init_rule("groups/pos0/moe/wo", (4, 16, 64), rules) == \
        ("matrix", round(math.log2(1 / 8 / W._ODD_RMS)))
    # "oe/wo" is not a suffix at a "/": the dense rule of a 2-D wo applies
    assert W.init_rule("groups/pos0/mlp/wo", (16, 64),
                       {"oe/wo": ("matrix", (1,))}) == \
        W.init_rule("groups/pos0/mlp/wo", (16, 64))


def write_bench(root, reference):
    conf = {"program_arch": "phi3-mini-3.8b"}
    if reference is not None:
        conf["reference"] = reference
    with open(os.path.join(root, "conf.json"), "w") as f:
        json.dump(conf, f)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump({"configs": [{"name": "c", "file": "conf.json"}],
                   "workloads": [{"name": "phi3-chat", "config": "c",
                                  "traffic": "phi3-chat", "chips": 1}],
                   "end_to_end": [], "per_layer": []}, f)


@pytest.mark.parametrize("reference", ["no_such_family", "../run", None])
def test_unknown_reference_fails_at_load_cell(tmp_path, monkeypatch, capsys,
                                              reference):
    write_bench(tmp_path, reference)
    monkeypatch.setattr(RUN, "ROOT", str(tmp_path))
    with pytest.raises(SystemExit) as e:
        RUN.load_cell("phi3-chat")
    assert e.value.code == 2
    assert f"names reference {reference!r}" in capsys.readouterr().err


def test_incomplete_reference_fails_at_load_cell(tmp_path, monkeypatch,
                                                 capsys):
    write_bench(tmp_path, "moe_rules")
    monkeypatch.setattr(RUN, "ROOT", str(tmp_path))
    monkeypatch.setattr(RUN, "REFERENCES", DATA)
    with pytest.raises(SystemExit):
        RUN.load_cell("phi3-chat")
    assert "lacks logits_at, smoke_file, check_program" in \
        capsys.readouterr().err


def test_a_reference_is_loaded_once():
    assert RUN.reference("dense") is RUN.reference("dense", RUN.REFERENCES)
    assert RUN.reference("moe_rules", DATA) is \
        RUN.reference("moe_rules", DATA + "/")


def test_a_metric_reads_the_models_own_counts():
    """A later metric file calls ``mfu(run, ..., run.ref.decode_flops)``:
    the fixture counts a token through its top-k experts alone."""
    fixture = RUN.reference("moe_rules", DATA)
    m = fixture.dims({"num_hidden_layers": 26, "hidden_size": 2048,
                      "num_attention_heads": 16, "num_key_value_heads": 16,
                      "head_dim": 128, "n_routed_experts": 64,
                      "num_experts_per_tok": 6, "n_shared_experts": 2,
                      "moe_intermediate_size": 1408, "vocab_size": 102400})
    every = dict(m, top_k=m["experts"])
    routed = fixture.token_params(every) - fixture.token_params(m)
    assert routed == (64 - 6) * 3 * 2048 * 1408
    run = RUN.Run(seconds=1.0, window=(0.0, 1.0), t0=0.0, arrivals=[],
                  reqs={}, steps=[Step("decode", 0.2, 0.3, (100, 300),
                                       traced=True)],
                  setup_s=0.0, model=m, peak={"bf16_flops_per_s": 1e12},
                  trace={"modules": {"jit__decode_impl": 0.5}}, ref=fixture)
    assert mfu(run, "decode", "jit__decode_impl", run.ref.decode_flops) == \
        pytest.approx(100 * fixture.decode_flops(m, (100, 300)) / 0.5e12)
