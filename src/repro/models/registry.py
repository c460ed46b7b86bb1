"""Top-level model API: specs, init, and the three forward modes.

Batch dicts:
  train:   {"tokens": (B,S), "targets": (B,S)}                (+frontend)
  prefill: {"tokens": (B,S)}                                  (+frontend)
  decode:  {"tokens": (B,), "positions": (B,)} + cache
Frontend stubs (per assignment: modality frontends provide precomputed
embeddings): vlm adds {"patch_embeds": (B,S_img,1024)}; audio replaces
tokens at prefill with {"frames": (B,T,128)} (encoder input).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ENC_ATTN, ArchConfig
from repro.distributed.sharding import shard
from repro.models import param as P
from repro.models import transformer as T
from repro.models.attention import make_kv_cache_specs
from repro.models.layers import apply_norm, embed_specs, embed_tokens, norm_specs, unembed
from repro.models.param import Spec

FRONTEND_DIMS = {"patch": 1024, "frame": 128}


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------
def model_specs(cfg: ArchConfig) -> dict:
    specs: dict = {"embed": embed_specs(cfg)}
    if cfg.embed_frontend:
        din = FRONTEND_DIMS[cfg.embed_frontend]
        specs["frontend"] = {"proj": Spec((din, cfg.d_model), (None, "embed"))}
    if cfg.enc_dec:
        specs["enc_groups"] = T.stack_block_specs(cfg, (ENC_ATTN,), cfg.num_encoder_layers)
        specs["enc_norm"] = norm_specs(cfg)
        specs["groups"] = T.stack_block_specs(cfg, cfg.resolved_pattern, cfg.n_groups, cross=True)
    else:
        specs["groups"] = T.stack_block_specs(cfg, cfg.resolved_pattern, cfg.n_groups)
    specs["final_norm"] = norm_specs(cfg)
    if not cfg.tie_embeddings:
        # (vocab, embed) layout: vocab takes "model", embed takes "data" --
        # fully sharded storage and a clean contraction in the loss.
        specs["unembed"] = {"kernel": Spec((cfg.vocab_size, cfg.d_model), ("vocab", "embed"))}
    return specs


def cache_specs(cfg: ArchConfig, batch: int, max_len: int,
                enc_len: Optional[int] = None) -> dict:
    per_pos = {}
    for i, kind in enumerate(cfg.resolved_pattern):
        c = T.cache_specs_for_kind(cfg, kind, batch, max_len)
        if cfg.enc_dec:
            kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
            c = dict(c,
                     ek=Spec((batch, enc_len, kv, hd), ("batch", "kv_seq", "kv_heads", "head_dim"), jnp.bfloat16, "zeros"),
                     ev=Spec((batch, enc_len, kv, hd), ("batch", "kv_seq", "kv_heads", "head_dim"), jnp.bfloat16, "zeros"))
        per_pos[f"pos{i}"] = c
    return P.stack_specs(per_pos, cfg.n_groups)


def init_params(cfg: ArchConfig, key):
    return P.init_tree(model_specs(cfg), key)


def abstract_params(cfg: ArchConfig):
    return P.abstract_tree(model_specs(cfg))


def param_axes(cfg: ArchConfig):
    return P.axes_tree(model_specs(cfg))


def count_params(cfg: ArchConfig, active: bool = False) -> int:
    specs = model_specs(cfg)
    flat = jax.tree_util.tree_flatten_with_path(specs, is_leaf=P.is_spec)[0]
    total = 0
    for path, s in flat:
        keys = [getattr(k, "key", str(k)) for k in path]
        n = int(np.prod(s.shape))
        if active and cfg.moe is not None and "moe" in keys and "shared" not in keys \
                and keys[-1] in ("wi_0", "wi_1", "wo"):
            n = n * cfg.moe.top_k // cfg.moe.num_experts
        total += n
    return total


# ---------------------------------------------------------------------------
# Forward modes
# ---------------------------------------------------------------------------
def _embed_input(cfg: ArchConfig, params: dict, batch: dict):
    """-> (x (B,S,D), positions (S,) or (B,S))."""
    if "patch_embeds" in batch:
        pe = jnp.einsum("bsd,de->bse", batch["patch_embeds"].astype(jnp.bfloat16),
                        params["frontend"]["proj"])
        te = embed_tokens(cfg, params["embed"], batch["tokens"])
        x = jnp.concatenate([pe, te], axis=1)
    else:
        x = embed_tokens(cfg, params["embed"], batch["tokens"])
    return shard(x, "batch", "res_seq", "embed"), jnp.arange(x.shape[1])


def _encode(cfg: ArchConfig, params: dict, frames, *, impl, remat=True):
    x = jnp.einsum("btd,de->bte", frames.astype(jnp.bfloat16),
                   params["frontend"]["proj"])
    x = T.run_stack_seq(cfg, params["enc_groups"], x,
                        positions=jnp.arange(x.shape[1]), impl=impl,
                        remat=remat, pattern=(ENC_ATTN,))
    return apply_norm(cfg, params["enc_norm"], x)


def lm_hidden(cfg: ArchConfig, params: dict, batch: dict, *, impl: str = "auto",
              moe_impl: str = "dispatch", remat: bool = True) -> jax.Array:
    """Training/eval forward -> final hidden states (B,S,D)."""
    enc_out = None
    if cfg.enc_dec:
        enc_out = _encode(cfg, params, batch["frames"], impl=impl, remat=remat)
    x, positions = _embed_input(cfg, params, batch)
    x = T.run_stack_seq(cfg, params["groups"], x, positions=positions,
                        impl=impl, moe_impl=moe_impl, remat=remat,
                        enc_out=enc_out)
    return apply_norm(cfg, params["final_norm"], x)


def lm_logits(cfg: ArchConfig, params: dict, batch: dict, **kw) -> jax.Array:
    return unembed(cfg, params, lm_hidden(cfg, params, batch, **kw))


def prefill(cfg: ArchConfig, params: dict, batch: dict, max_len: int, *,
            impl: str = "auto", moe_impl: str = "dispatch", lengths=None):
    """-> (last-position logits (B,V), decode cache, next positions (B,)).

    ``lengths`` (B,) supports right-padded ragged prompts: logits are taken
    at ``lengths-1``; pad K/V slots carry positions >= length so decode
    masks them out.
    """
    enc_out = None
    if cfg.enc_dec:
        enc_out = _encode(cfg, params, batch["frames"], impl=impl, remat=False)
    x, positions = _embed_input(cfg, params, batch)
    x, cache = T.run_stack_prefill(cfg, params["groups"], x, positions=positions,
                                   max_len=max_len, impl=impl, moe_impl=moe_impl,
                                   enc_out=enc_out)
    x = apply_norm(cfg, params["final_norm"], x)
    b, s = x.shape[0], x.shape[1]
    if lengths is None:
        lengths = jnp.full((b,), s, jnp.int32)
        x_last = x[:, -1, :]
    else:
        lengths = lengths.astype(jnp.int32)
        x_last = jnp.take_along_axis(
            x, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0, :]
    logits = unembed(cfg, params, x_last)
    return logits, cache, lengths


def decode_layouts(cache: dict) -> dict:
    """The layouts a cache's arrays have at rest, for ``decode_step``."""
    return jax.tree_util.tree_map(lambda c: c.format.layout, cache)


def decode_step(cfg: ArchConfig, params: dict, cache: dict, tokens, positions, *,
                cache_layouts, impl: str = "auto", moe_impl: str = "dispatch",
                enc_lengths=None):
    """tokens: (B,), positions: (B,) -> (logits (B,V), new cache).

    The cache is updated in place when donated.  ``cache_layouts``: its
    leaves' layouts at rest, or None (see ``transformer.run_stack_decode``);
    ``decode_layouts`` reads them off the arrays."""
    x = embed_tokens(cfg, params["embed"], tokens)
    if cfg.enc_dec and enc_lengths is None:
        # full encoder context by default (benchmarks)
        enc_len = cache["pos0"]["ek"].shape[2]
        enc_lengths = jnp.full((tokens.shape[0],), enc_len, jnp.int32)
    x, new_cache = T.run_stack_decode(cfg, params["groups"], x, cache,
                                      positions=positions,
                                      cache_layouts=cache_layouts, impl=impl,
                                      moe_impl=moe_impl, enc_lengths=enc_lengths)
    x = apply_norm(cfg, params["final_norm"], x)
    return unembed(cfg, params, x), new_cache
