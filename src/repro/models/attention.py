"""Attention: GQA self/cross attention for train, prefill and decode.

Reference path is a query-chunked (flash-style) jnp implementation — memory
safe at 32k prefill and exact (it is also the oracle the Pallas kernels are
validated against; tiny shapes additionally check the naive materializing
form).  ``impl="pallas"`` dispatches to the TPU kernels in repro.kernels.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.sharding import shard
from repro.models.layers import rms_norm, rope
from repro.models.param import Spec

F32 = jnp.float32
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Param specs
# ---------------------------------------------------------------------------
def attention_specs(cfg: ArchConfig, cross: bool = False) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    out = {
        "q": Spec((d, h, hd), ("embed", "heads", "head_dim")),
        "k": Spec((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "v": Spec((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "o": Spec((h, hd, d), ("heads", "head_dim", "embed")),
    }
    if cfg.use_bias:
        out["qb"] = Spec((h, hd), ("heads", "head_dim"), jnp.float32, "zeros")
        out["kb"] = Spec((kv, hd), ("kv_heads", "head_dim"), jnp.float32, "zeros")
        out["vb"] = Spec((kv, hd), ("kv_heads", "head_dim"), jnp.float32, "zeros")
        out["ob"] = Spec((d,), ("embed",), jnp.float32, "zeros")
    if cfg.qk_norm and not cross:
        out["q_norm"] = Spec((hd,), ("head_dim",), jnp.float32, "ones")
        out["k_norm"] = Spec((hd,), ("head_dim",), jnp.float32, "ones")
    return out


# ---------------------------------------------------------------------------
# Full block-level application (projections + rope + cache plumbing)
# ---------------------------------------------------------------------------
def _proj_qkv(cfg, p, x, xa=None):
    src = x if xa is None else xa
    q = jnp.einsum("...d,dhk->...hk", x, p["q"])
    k = jnp.einsum("...d,dhk->...hk", src, p["k"])
    v = jnp.einsum("...d,dhk->...hk", src, p["v"])
    if "qb" in p:
        q, k, v = q + p["qb"].astype(q.dtype), k + p["kb"].astype(k.dtype), v + p["vb"].astype(v.dtype)
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    return q, k, v


def _out_proj(p, o):
    y = jnp.einsum("...hk,hkd->...d", o, p["o"])
    if "ob" in p:
        y = y + p["ob"].astype(y.dtype)
    return y


def self_attention(cfg: ArchConfig, p: dict, x: jax.Array, *,
                   positions: jax.Array, causal: bool = True,
                   window: Optional[int] = None, impl: str = "auto") -> jax.Array:
    """Full-sequence self attention (train / prefill / encoder)."""
    q, k, v = _proj_qkv(cfg, p, x)
    q = rope(q, positions, cfg.rope_theta, cfg.rope_fraction)
    k = rope(k, positions, cfg.rope_theta, cfg.rope_fraction)
    q = shard(q, "batch", "res_seq", "heads", "head_dim")
    k = shard(k, "batch", "res_seq", "kv_heads", "head_dim")
    from repro.kernels import ops
    o = ops.flash_attention(q, k, v, causal=causal, window=window, impl=impl)
    o = shard(o, "batch", "res_seq", "heads", "head_dim")
    return _out_proj(p, o)


def make_kv_cache_specs(cfg: ArchConfig, batch: int, max_len: int,
                        window: Optional[int] = None) -> dict:
    """Cache specs for one attention position.  SWA layers get a ring buffer."""
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    size = min(max_len, window) if window else max_len
    return {
        "k": Spec((batch, size, kv, hd), ("batch", "kv_seq", "kv_heads", "head_dim"), jnp.bfloat16, "zeros"),
        "v": Spec((batch, size, kv, hd), ("batch", "kv_seq", "kv_heads", "head_dim"), jnp.bfloat16, "zeros"),
        # absolute position held by each slot (-1 = empty); ring for SWA
        "pos": Spec((batch, size), ("batch", "kv_seq"), jnp.int32, "constant", -1),
    }


def _write_rows(leaf, rows, layer, slot):
    """Write ``rows[b]`` at ``[layer, b, slot[b]]`` of a stacked leaf in
    place, one dynamic-update-slice per batch slot (a scatter makes the TPU
    compiler relayout the whole stack around it), then read the layer.
    -> (the stack, the layer's slice)."""
    for i in range(rows.shape[0]):
        start = (layer, i, slot[i]) + (0,) * (rows.ndim - 1)
        leaf = jax.lax.dynamic_update_slice(leaf, rows[i][None, None, None],
                                            start)
    return leaf, jax.lax.dynamic_index_in_dim(leaf, layer, keepdims=False)


def _write_layer(leaf, rows, layer, slot):
    """``_write_rows`` as one write of the whole layer.  The CPU compiler
    widens a bf16 dynamic-update-slice's whole operand to f32, so there
    each row written would cost a pass over the stack."""
    old = jax.lax.dynamic_index_in_dim(leaf, layer, keepdims=False)
    hit = jnp.arange(old.shape[1])[None, :] == slot[:, None]
    new = jnp.where(hit.reshape(hit.shape + (1,) * (old.ndim - 2)),
                    rows[:, None], old)
    return jax.lax.dynamic_update_index_in_dim(leaf, new, layer, 0), new


def _write_token(leaf, rows, layer, slot):
    """The new token's rows into a stacked leaf, by the platform compiled
    for: each writer costs the other platform a multiple of its time (on
    the TPU a whole-layer write is one more pass over the layer).
    -> (the stack, the layer's slice with the rows in it)."""
    return jax.lax.platform_dependent(leaf, rows.astype(leaf.dtype), layer,
                                      slot, cpu=_write_layer,
                                      default=_write_rows)


def decode_self_attention(cfg: ArchConfig, p: dict, x: jax.Array, cache: dict,
                          layer: jax.Array, *, positions: jax.Array,
                          lengths: jax.Array, window: Optional[int] = None,
                          impl: str = "auto"):
    """One-token decode against the cache of every layer group.

    x: (B, D); positions: (B,).  ``cache`` leaves are stacked over groups
    (``k``/``v``: (G, B, T, KV, hd), ``pos``: (G, B, T)); this layer writes
    only its new token, one row per batch slot at ``[layer, b, positions %
    T]`` (a ring for SWA), so a donated stack is updated in place.  It then
    attends over its own slice, new token included.  Returns the output and
    the stack with the rows written; other leaves pass through.
    """
    q, k, v = _proj_qkv(cfg, p, x[:, None, :])          # (B,1,H,hd)
    q = rope(q, positions[:, None], cfg.rope_theta, cfg.rope_fraction)[:, 0]
    k = rope(k, positions[:, None], cfg.rope_theta, cfg.rope_fraction)[:, 0]
    v = v[:, 0]
    slot = positions % cache["k"].shape[2]               # ring for SWA, id for full
    kv_axes = ("kv_seq", "kv_heads", "head_dim")
    k_all, k_l = _write_token(cache["k"], k, layer, slot)
    v_all, v_l = _write_token(cache["v"], v, layer, slot)
    pos_all, pos_l = _write_token(cache["pos"], positions, layer, slot)
    new_cache = dict(cache,
                     k=shard(k_all, "layer", "batch", *kv_axes),
                     v=shard(v_all, "layer", "batch", *kv_axes),
                     pos=shard(pos_all, "layer", "batch", "kv_seq"))
    from repro.kernels import ops
    o = ops.decode_attention(q, shard(k_l, "batch", *kv_axes),
                             shard(v_l, "batch", *kv_axes), lengths=lengths,
                             key_positions=pos_l, q_pos=positions,
                             window=window, impl=impl)
    return _out_proj(p, o), new_cache


def cross_kv(p: dict, enc_out: jax.Array):
    """Project encoder output to cross-attention K/V. enc_out: (B,T,D)."""
    k = jnp.einsum("btd,dhk->bthk", enc_out, p["k"])
    v = jnp.einsum("btd,dhk->bthk", enc_out, p["v"])
    if "kb" in p:
        k, v = k + p["kb"].astype(k.dtype), v + p["vb"].astype(v.dtype)
    return k, v


def cross_attention_seq(cfg: ArchConfig, p: dict, x: jax.Array,
                        enc_out: jax.Array, impl: str = "auto"):
    """Decoder cross-attention (full dec sequence) over encoder output."""
    q = jnp.einsum("...d,dhk->...hk", x, p["q"])
    if "qb" in p:
        q = q + p["qb"].astype(q.dtype)
    k, v = cross_kv(p, enc_out)
    from repro.kernels import ops
    o = ops.flash_attention(q, k, v, causal=False, impl=impl)
    return _out_proj(p, o)


def cross_attention_decode(cfg: ArchConfig, p: dict, x: jax.Array, ek, ev,
                           enc_lengths: jax.Array, impl: str = "auto"):
    """Single-token cross-attention over cached encoder K/V. x: (B,D)."""
    q = jnp.einsum("bd,dhk->bhk", x, p["q"])
    if "qb" in p:
        q = q + p["qb"].astype(q.dtype)
    from repro.kernels import ops
    o = ops.decode_attention(q, ek, ev, lengths=enc_lengths, impl=impl)
    return _out_proj(p, o)
