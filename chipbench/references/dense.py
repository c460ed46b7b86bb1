"""Plain float32 forward of the dense decoder family, from a configuration
file and a seed alone.

RMSNorm or LayerNorm, full or partial rotary embedding (rotate-half
convention, as the Hugging Face implementations), multi-head or grouped
attention with a causal and an optional sliding-window mask, SwiGLU MLP,
untied output head.  It reads the weights that ``chipbench.weights``
makes for the served tree's leaf paths, one layer at a time, and imports
nothing of the program under test.  Matrix products run at ``highest``
precision, so on a TPU they are float32 and not bfloat16.

``control=True`` computes the same forward with float8 (e4m3) operands in
every linear layer, each row of activations and each output column of
weights scaled to the format's range: the step below the bfloat16 that
the served configuration states.

A configuration file names its reference (``"reference": "dense"``); the
harness finds it as ``chipbench/references/<name>.py``.  Every reference
module provides what the harness reads (``run.REFERENCE_API``): ``dims``,
``logits_at``, ``smoke_file``, ``check_program`` and ``leaf_rules``.  It
also holds the model counts that its metric files read through
``run.ref``: here ``prefill_flops`` and ``decode_flops``, with the
(flops, bytes) of each kernel it names.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as W
from chipbench.counts import (decode_attention, decode_flops,  # noqa: F401
                              flash_attention, prefill_flops)

F32 = jnp.float32
_E4M3_MAX = 448.0

#: weight rules by path suffix; ``weights.init_rule``'s own rules cover
#: every leaf of this family
leaf_rules: dict = {}


def dims(cfg: dict) -> dict:
    """Sizes the forward needs, from a configuration file's keys."""
    h = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // h
    layernorm = "layer_norm_eps" in cfg
    return {
        "layers": cfg["num_hidden_layers"], "d": cfg["hidden_size"],
        "heads": h, "kv_heads": cfg["num_key_value_heads"], "head_dim": hd,
        "d_ff": cfg["intermediate_size"], "vocab": cfg["vocab_size"],
        "layernorm": layernorm,
        "eps": cfg["layer_norm_eps"] if layernorm else cfg["rms_norm_eps"],
        "theta": float(cfg["rope_theta"]),
        "rotary": int(hd * cfg.get("partial_rotary_factor", 1.0)) // 2 * 2,
        "window": cfg.get("sliding_window"),
    }


def smoke_file(c) -> dict:
    """The configuration file's keys at the sizes of the program's config
    ``c`` (its -smoke config, for a rehearsal on the CPU)."""
    out = {"num_hidden_layers": c.num_layers, "hidden_size": c.d_model,
           "num_attention_heads": c.num_heads,
           "num_key_value_heads": c.num_kv_heads, "head_dim": c.head_dim,
           "intermediate_size": c.d_ff, "vocab_size": c.vocab_size,
           "rope_theta": c.rope_theta,
           "partial_rotary_factor": c.rope_fraction}
    # the program fixes its norm epsilon at 1e-6
    out["layer_norm_eps" if c.norm == "layernorm" else "rms_norm_eps"] = 1e-6
    return out


def check_program(c, cfg: dict) -> dict:
    """-> {field: (program's value, file's value)} where the program's
    config ``c`` is not the one the configuration file states."""
    want = {"num_layers": "num_hidden_layers", "d_model": "hidden_size",
            "num_heads": "num_attention_heads",
            "num_kv_heads": "num_key_value_heads", "d_ff": "intermediate_size",
            "vocab_size": "vocab_size", "resolved_head_dim": "head_dim",
            "rope_theta": "rope_theta"}
    bad = {a: (getattr(c, a), cfg[k]) for a, k in want.items()
           if getattr(c, a) != cfg[k]}
    frac = cfg.get("partial_rotary_factor", 1.0)
    if c.rope_fraction != frac:
        bad["rope_fraction"] = (c.rope_fraction, frac)
    norm = "layernorm" if "layer_norm_eps" in cfg else "rmsnorm"
    if c.norm != norm:
        bad["norm"] = (c.norm, norm)
    return bad


def _leaves(m: dict) -> dict:
    """Leaf path -> one layer's shape, for the paths the forward reads."""
    d, h, kv, hd, f = m["d"], m["heads"], m["kv_heads"], m["head_dim"], m["d_ff"]
    out = {"attn/q": (d, h, hd), "attn/k": (d, kv, hd), "attn/v": (d, kv, hd),
           "attn/o": (h, hd, d), "mlp/wi_0": (d, f), "mlp/wi_1": (d, f),
           "mlp/wo": (f, d)}
    norms = ("scale", "bias") if m["layernorm"] else ("scale",)
    for n in ("norm1", "norm2"):
        for p in norms:
            out[f"{n}/{p}"] = (d,)
    return out


def _quant(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / _E4M3_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(x, w, control):
    """x (..., K) @ w (K, N) in float32, or on float8 operands."""
    if control:
        x, w = _quant(x, -1), _quant(w, 0)
    return jnp.matmul(x, w, precision="highest")


def _norm(m, x, scale, bias):
    if m["layernorm"]:
        x = x - jnp.mean(x, -1, keepdims=True)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + m["eps"])
    x = x * scale
    return x + bias if bias is not None else x


def _rope(m, x, pos):
    """x (T, H, hd); rotate-half on the first ``rotary`` dims."""
    r = m["rotary"]
    if r == 0:
        return x
    inv = 1.0 / (m["theta"] ** (jnp.arange(0, r, 2, dtype=F32) / r))
    ang = pos[:, None].astype(F32) * inv                     # (T, r/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    xr = x[..., :r]
    half = r // 2
    rot = jnp.concatenate([-xr[..., half:], xr[..., :half]], -1)
    return jnp.concatenate([xr * cos + rot * sin, x[..., r:]], -1)


def _attend(m, q, k, v):
    """One sequence: q (T, H, hd), k/v (T, KV, hd) -> (T, H, hd)."""
    t = q.shape[0]
    g = m["heads"] // m["kv_heads"]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("thk,shk->hts", q, k, precision="highest")
    s = s / np.sqrt(m["head_dim"])
    i = jnp.arange(t)[:, None]
    j = jnp.arange(t)[None, :]
    ok = j <= i
    if m["window"]:
        ok &= i - j < m["window"]
    s = jnp.where(ok[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hts,shk->thk", p, v, precision="highest")


@functools.partial(jax.jit, static_argnames=("mk", "control"))
def _block(x, w, mk, control):
    m = dict(mk)
    n, t, d = x.shape
    h, kv, hd = m["heads"], m["kv_heads"], m["head_dim"]
    pos = jnp.arange(t)
    a = _norm(m, x, w["norm1/scale"], w.get("norm1/bias"))
    q = _mm(a, w["attn/q"].reshape(d, h * hd), control).reshape(n, t, h, hd)
    k = _mm(a, w["attn/k"].reshape(d, kv * hd), control).reshape(n, t, kv, hd)
    v = _mm(a, w["attn/v"].reshape(d, kv * hd), control).reshape(n, t, kv, hd)
    q = jax.vmap(lambda z: _rope(m, z, pos))(q)
    k = jax.vmap(lambda z: _rope(m, z, pos))(k)
    o = jax.lax.map(lambda qkv: _attend(m, *qkv), (q, k, v))
    x = x + _mm(o.reshape(n, t, h * hd), w["attn/o"].reshape(h * hd, d),
                control)
    a = _norm(m, x, w["norm2/scale"], w.get("norm2/bias"))
    gate = jax.nn.silu(_mm(a, w["mlp/wi_0"], control))
    return x + _mm(gate * _mm(a, w["mlp/wi_1"], control), w["mlp/wo"],
                   control)


@functools.partial(jax.jit, static_argnames=("mk",))
def _layer_weights(key, layer, mk):
    m = dict(mk)
    return {p: W.make_leaf(key, "groups/pos0/" + p, s, F32, layer,
                           leaf_rules)
            for p, s in _leaves(m).items()}


@functools.partial(jax.jit, static_argnames=("mk",))
def _embed(key, tokens, mk):
    m = dict(mk)
    table = W.make_leaf(key, "embed/tokens", (m["vocab"], m["d"]), F32,
                        rules=leaf_rules)
    return table[tokens]


@functools.partial(jax.jit, static_argnames=("mk", "control"))
def _logits(key, x, rows, mk, control):
    """Logits at ``rows`` (N, P) of each sequence of ``x`` (N, T, d)."""
    m = dict(mk)
    d = m["d"]
    fs = W.make_leaf(key, "final_norm/scale", (d,), F32, rules=leaf_rules)
    fb = (W.make_leaf(key, "final_norm/bias", (d,), F32, rules=leaf_rules)
          if m["layernorm"] else None)
    head = W.make_leaf(key, "unembed/kernel", (m["vocab"], d), F32,
                       rules=leaf_rules)
    h = jnp.take_along_axis(x, rows[..., None], axis=1)      # (N, P, d)
    h = _norm(m, h, fs, fb)
    return _mm(h, head.T, control)


def logits_at(cfg: dict, seed: int, tokens: np.ndarray, rows: np.ndarray,
              control: bool = False) -> jax.Array:
    """Run the forward over ``tokens`` (N, T), right-padded (causal masking
    keeps padding out of every earlier position), and return the float32
    logits at positions ``rows`` (N, P) -> (N, P, vocab)."""
    m = dims(cfg)
    mk = tuple(sorted(m.items()))
    key = W.seed_key(seed)
    with jax.default_matmul_precision("highest"):
        x = _embed(key, jnp.asarray(tokens, jnp.int32), mk)
        for layer in range(m["layers"]):
            w = _layer_weights(key, np.uint32(layer), mk)
            x = _block(x, w, mk, control)
        return _logits(key, x, jnp.asarray(rows, jnp.int32), mk, control)
