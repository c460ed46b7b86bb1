"""A reference module's weight rules and model counts for the program's
fine-grained MoE family (``deepseek-moe-16b``: routed experts of their own
width, top-k, shared experts), kept as test data: it shows that a new
architecture brings these as files of its own and leaves the harness as
it is.  It holds no forward, so ``run.load_cell`` would refuse it."""

#: path suffix -> (kind, axes whose product is the fan-in); the shared
#: experts' 2-D ``moe/shared/wi_0`` and the attention leaves keep
#: ``weights.init_rule``'s own rules
leaf_rules = {
    "moe/router": ("matrix", (0,)),   # (d_model, experts)
    "moe/wi_0": ("matrix", (1,)),     # (experts, d_model, expert width)
    "moe/wi_1": ("matrix", (1,)),
    "moe/wo": ("matrix", (1,)),       # (experts, expert width, d_model)
}


def dims(cfg: dict) -> dict:
    """Sizes the counts need, from a configuration file's keys."""
    h = cfg["num_attention_heads"]
    return {"layers": cfg["num_hidden_layers"], "d": cfg["hidden_size"],
            "heads": h, "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg.get("head_dim") or cfg["hidden_size"] // h,
            "experts": cfg["n_routed_experts"],
            "top_k": cfg["num_experts_per_tok"],
            "shared": cfg["n_shared_experts"],
            "expert_ff": cfg["moe_intermediate_size"],
            "vocab": cfg["vocab_size"]}


def token_params(m: dict) -> int:
    """Matrix parameters one token passes through in one layer: attention,
    the router, the shared experts and only the ``top_k`` routed experts
    it is sent to."""
    d, hd = m["d"], m["head_dim"]
    attn = d * hd * (2 * m["heads"] + 2 * m["kv_heads"])
    return (attn + d * m["experts"]
            + (m["shared"] + m["top_k"]) * 3 * d * m["expert_ff"])


def decode_flops(m: dict, contexts) -> int:
    """Model FLOPs of one decode step over the active slots."""
    per_token = 2 * m["layers"] * token_params(m) + 2 * m["vocab"] * m["d"]
    attn = sum(m["layers"] * 4 * m["heads"] * m["head_dim"] * c
               for c in contexts)
    return len(contexts) * per_token + attn
