"""Decoder stack assembly: pattern-scanned heterogeneous blocks.

A model is ``embed -> scan(groups) -> final_norm`` where one *group* is one
repetition of ``cfg.resolved_pattern`` (e.g. gemma3: 5 SWA + 1 global attn;
jamba: 7 mamba + 1 attn, MoE on odd positions).  Params and caches are
stacked along a leading "layer" axis so HLO size is O(|pattern|), not
O(num_layers) — this keeps 512-device compiles fast and is how real JAX
frameworks (MaxText et al.) scale depth.

Decode carries the whole stacked cache through the group scan and updates
it in place: each layer writes only its new token (or its recurrent state)
at its group index, so a step never builds a second cache.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.layout import with_layout_constraint

from repro.configs.base import ATTN, ATTN_SWA, ENC_ATTN, MAMBA, ArchConfig
from repro.distributed.sharding import shard
from repro.models import attention as attn_mod
from repro.models import mamba as mamba_mod
from repro.models import moe as moe_mod
from repro.models.layers import apply_mlp, apply_norm, mlp_specs, norm_specs
from repro.models.param import Spec, stack_specs
from repro.util import cost_mode


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------
def block_specs(cfg: ArchConfig, pos: int, kind: str, cross: bool = False) -> dict:
    out = {"norm1": norm_specs(cfg)}
    if kind == MAMBA:
        out["mamba"] = mamba_mod.mamba_specs(cfg)
    else:
        out["attn"] = attn_mod.attention_specs(cfg)
    if cross:
        out["xnorm"] = norm_specs(cfg)
        out["xattn"] = attn_mod.attention_specs(cfg, cross=True)
    is_moe = cfg.moe is not None and cfg.moe_positions and pos in cfg.moe_positions
    has_ffn = cfg.d_ff > 0 or is_moe
    if has_ffn:
        if not cfg.parallel_block:
            out["norm2"] = norm_specs(cfg)
        out["moe" if is_moe else "mlp"] = (
            moe_mod.moe_specs(cfg) if is_moe else mlp_specs(cfg)
        )
    return out


def stack_block_specs(cfg: ArchConfig, pattern, n_groups: int, cross=False) -> dict:
    per_pos = {f"pos{i}": block_specs(cfg, i, kind, cross=cross)
               for i, kind in enumerate(pattern)}
    return stack_specs(per_pos, n_groups)


def cache_specs_for_kind(cfg: ArchConfig, kind: str, batch: int, max_len: int) -> dict:
    if kind == MAMBA:
        return mamba_mod.mamba_cache_specs(cfg, batch)
    window = cfg.sliding_window if kind == ATTN_SWA else None
    return attn_mod.make_kv_cache_specs(cfg, batch, max_len, window=window)


def stack_cache_specs(cfg: ArchConfig, batch: int, max_len: int) -> dict:
    pattern = cfg.resolved_pattern
    per_pos = {f"pos{i}": cache_specs_for_kind(cfg, kind, batch, max_len)
               for i, kind in enumerate(pattern)}
    return stack_specs(per_pos, cfg.n_groups)


# ---------------------------------------------------------------------------
# Block application
# ---------------------------------------------------------------------------
def _ffn(cfg, p, x, moe_impl):
    if "moe" in p:
        return moe_mod.apply_moe(cfg, p["moe"], x, impl=moe_impl)
    if "mlp" in p:
        return apply_mlp(cfg, p["mlp"], x)
    return jnp.zeros_like(x)


def apply_block_seq(cfg: ArchConfig, p: dict, kind: str, x: jax.Array, *,
                    positions: jax.Array, impl: str, moe_impl: str,
                    enc_out=None) -> jax.Array:
    h = apply_norm(cfg, p["norm1"], x)
    if kind == MAMBA:
        mix = mamba_mod.apply_mamba(cfg, p["mamba"], h, impl=impl)
    else:
        window = cfg.sliding_window if kind == ATTN_SWA else None
        mix = attn_mod.self_attention(cfg, p["attn"], h, positions=positions,
                                      causal=(kind != ENC_ATTN), window=window,
                                      impl=impl)
    if cfg.parallel_block:
        return shard(x + mix + _ffn(cfg, p, h, moe_impl),
                     "batch", "res_seq", "embed")
    x = x + mix
    if "xattn" in p:
        hx = apply_norm(cfg, p["xnorm"], x)
        x = x + attn_mod.cross_attention_seq(cfg, p["xattn"], hx, enc_out, impl=impl)
    if "norm2" in p:
        h2 = apply_norm(cfg, p["norm2"], x)
        x = x + _ffn(cfg, p, h2, moe_impl)
    return shard(x, "batch", "res_seq", "embed")


def apply_block_decode(cfg: ArchConfig, p: dict, kind: str, x: jax.Array,
                       cache: dict, layer: jax.Array, *, positions: jax.Array,
                       impl: str, moe_impl: str, enc_lengths=None):
    """x: (B, D) single token.  ``cache``: this position's leaves stacked
    over groups; ``layer`` is the group index.  Returns the stack with the
    block's new entries written at ``layer``."""
    h = apply_norm(cfg, p["norm1"], x)
    if kind == MAMBA:
        mix, state = mamba_mod.decode_mamba(cfg, p["mamba"], h,
                                            _layer_slice(cache, layer))
        cache = {n: jax.lax.dynamic_update_index_in_dim(
                     c, state[n].astype(c.dtype), layer, 0)
                 for n, c in cache.items()}
    else:
        window = cfg.sliding_window if kind == ATTN_SWA else None
        mix, cache = attn_mod.decode_self_attention(
            cfg, p["attn"], h, cache, layer, positions=positions,
            lengths=positions + 1, window=window, impl=impl)
    if cfg.parallel_block:
        return x + mix + _ffn(cfg, p, h, moe_impl), cache
    x = x + mix
    if "xattn" in p:
        hx = apply_norm(cfg, p["xnorm"], x)
        enc = _layer_slice({"ek": cache["ek"], "ev": cache["ev"]}, layer)
        x = x + attn_mod.cross_attention_decode(cfg, p["xattn"], hx,
                                                enc["ek"], enc["ev"],
                                                enc_lengths, impl=impl)
    if "norm2" in p:
        h2 = apply_norm(cfg, p["norm2"], x)
        x = x + _ffn(cfg, p, h2, moe_impl)
    return x, cache


def _layer_slice(cache: dict, layer: jax.Array) -> dict:
    return {n: jax.lax.dynamic_index_in_dim(c, layer, keepdims=False)
            for n, c in cache.items()}


# ---------------------------------------------------------------------------
# Stack runners (scan over groups)
# ---------------------------------------------------------------------------
def run_stack_seq(cfg: ArchConfig, groups: dict, x: jax.Array, *,
                  positions: jax.Array, impl: str = "auto",
                  moe_impl: str = "dispatch", remat: bool = True,
                  pattern=None, enc_out=None) -> jax.Array:
    pattern = pattern or cfg.resolved_pattern

    def group_fn(carry, gp):
        h = carry
        for i, kind in enumerate(pattern):
            h = apply_block_seq(cfg, gp[f"pos{i}"], kind, h,
                                positions=positions, impl=impl,
                                moe_impl=moe_impl, enc_out=enc_out)
        return h, None

    if remat:
        from repro.util import opt_flags
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if "remat_dots" in opt_flags() else None)
        body = jax.checkpoint(group_fn, policy=policy)
    else:
        body = group_fn
    x, _ = jax.lax.scan(body, x, groups, unroll=cost_mode())
    return x


def run_stack_prefill(cfg: ArchConfig, groups: dict, x: jax.Array, *,
                      positions: jax.Array, max_len: int, impl: str = "auto",
                      moe_impl: str = "dispatch", pattern=None, enc_out=None):
    """Like seq, but also emits per-position decode caches (scan ys)."""
    pattern = pattern or cfg.resolved_pattern

    def group_fn(carry, gp):
        h = carry
        caches = {}
        for i, kind in enumerate(pattern):
            p = gp[f"pos{i}"]
            h_new = apply_block_seq(cfg, p, kind, h, positions=positions,
                                    impl=impl, moe_impl=moe_impl,
                                    enc_out=enc_out)
            caches[f"pos{i}"] = _prefill_cache(cfg, p, kind, h, positions,
                                               max_len, impl, enc_out=enc_out)
            h = h_new
        return h, caches

    x, caches = jax.lax.scan(group_fn, x, groups, unroll=cost_mode())
    return x, caches


def _prefill_cache(cfg, p, kind, h_in, positions, max_len, impl, enc_out=None):
    """Build the decode cache entry for one block from its prefill input."""
    b, s, _ = h_in.shape
    if kind == MAMBA:
        hn = apply_norm(cfg, p["norm1"], h_in)
        return _mamba_prefill_cache(cfg, p["mamba"], hn)
    window = cfg.sliding_window if kind == ATTN_SWA else None
    hn = apply_norm(cfg, p["norm1"], h_in)
    _, k, v = attn_mod._proj_qkv(cfg, p["attn"], hn)
    from repro.models.layers import rope
    k = rope(k, positions, cfg.rope_theta, cfg.rope_fraction)
    size = min(max_len, window) if window else max_len
    if size >= s:
        pad = size - s
        kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))).astype(jnp.bfloat16)
        vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))).astype(jnp.bfloat16)
        pos = jnp.pad(jnp.broadcast_to(positions, (b, s)), ((0, 0), (0, pad)),
                      constant_values=-1)
    else:  # ring: keep last `size`, placed at slot = pos % size
        import numpy as np
        last = np.arange(s - size, s)
        slot_of = np.zeros(size, np.int64)
        slot_of[last % size] = last
        kc = k[:, slot_of].astype(jnp.bfloat16)
        vc = v[:, slot_of].astype(jnp.bfloat16)
        pos = jnp.broadcast_to(jnp.asarray(slot_of, jnp.int32), (b, size))
    kc = shard(kc, "batch", "kv_seq", "kv_heads", "head_dim")
    vc = shard(vc, "batch", "kv_seq", "kv_heads", "head_dim")
    out = {"k": kc, "v": vc, "pos": pos}
    if "xattn" in p:
        ek, ev = attn_mod.cross_kv(p["xattn"], enc_out)
        out["ek"], out["ev"] = ek.astype(jnp.bfloat16), ev.astype(jnp.bfloat16)
    return out


def _mamba_prefill_cache(cfg, p, hn):
    """Run the mamba projections + SSD once more to get the final state."""
    m, di, nh, pd, n = mamba_mod._dims(cfg)
    b, s, _ = hn.shape
    z, xm, Bm, Cm, dt = mamba_mod._project(cfg, p, hn)
    xm = mamba_mod._causal_conv(xm, p["conv_x"], p["conv_bx"])
    Bmc = mamba_mod._causal_conv(Bm.reshape(b, s, -1), p["conv_B"], p["conv_bB"])
    Cmc = mamba_mod._causal_conv(Cm.reshape(b, s, -1), p["conv_C"], p["conv_bC"])
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    from repro.kernels import ops
    _, hstate = ops.ssd_scan(xm.reshape(b, s, nh, pd), dt, A,
                             Bmc.reshape(b, s, 1, n), Cmc.reshape(b, s, 1, n),
                             chunk=m.chunk, impl="ref")
    # conv caches: last (d_conv - 1) *pre-activation* inputs
    z2, x2, B2, C2, _ = mamba_mod._project(cfg, p, hn[:, -(m.d_conv - 1):, :])
    return {"h": hstate, "conv_x": x2.astype(jnp.bfloat16),
            "conv_B": B2.reshape(b, m.d_conv - 1, -1).astype(jnp.bfloat16),
            "conv_C": C2.reshape(b, m.d_conv - 1, -1).astype(jnp.bfloat16)}


def run_stack_decode(cfg: ArchConfig, groups: dict, x: jax.Array, cache: dict, *,
                     positions: jax.Array, cache_layouts, impl: str = "auto",
                     moe_impl: str = "dispatch", pattern=None, enc_lengths=None):
    """One decode token through every group.

    The stacked cache rides the scan's carry; the scan runs over the group
    parameters and the group index.  Each block writes only its new entries
    at its group (one K/V row and position per batch slot for attention,
    the recurrent state for mamba), so a donated cache is updated in place
    and no second cache is built.  Cross-attention K/V are only read.

    ``cache_layouts`` (a tree of ``jax.experimental.layout.Layout`` like
    ``cache``, from each leaf's ``format.layout``) holds the carry to the
    layouts the cache has at rest; the TPU's default layout of a leaf
    depends on its shape, so only the holder of the arrays knows it.
    ``None`` leaves the carry's layout to the compiler: the TPU compiler
    then gives it the layout the attention kernel reads and converts the
    whole stack into it before the loop and back after it.
    """
    pattern = pattern or cfg.resolved_pattern
    n_groups = jax.tree_util.tree_leaves(groups)[0].shape[0]
    pin = ((lambda c: with_layout_constraint(c, cache_layouts))
           if cache_layouts is not None else (lambda c: c))

    def group_fn(carry, xs):
        h, cache = carry
        gp, layer = xs
        cache = dict(cache)
        for i, kind in enumerate(pattern):
            key = f"pos{i}"
            h, cache[key] = apply_block_decode(
                cfg, gp[key], kind, h, cache[key], layer, positions=positions,
                impl=impl, moe_impl=moe_impl, enc_lengths=enc_lengths)
        return (h, pin(cache)), None

    (x, cache), _ = jax.lax.scan(group_fn, (x, cache),
                                 (groups, jnp.arange(n_groups)),
                                 unroll=cost_mode())
    return x, cache
