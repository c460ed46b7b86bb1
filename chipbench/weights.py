"""Weights made from a seed, exactly reproducible leaf by leaf and layer by
layer.

Every value is an odd integer in [-255, 255] times a power of two, so it
is exact in bfloat16 and in float32, and the served tree (one jitted call
on the device) and the reference (one layer at a time, after the window)
read the same numbers without sharing any array.  A leaf is named by its
path in the served tree ("groups/pos0/attn/q"); the path and, for the
stacked layer groups, the layer index pick the random stream.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

#: RMS of the odd integers 2i - 255, i uniform on 0..255
_ODD_RMS = math.sqrt(sum((2 * i - 255) ** 2 for i in range(256)) / 256)


def seed_key(seed: int) -> jax.Array:
    """Threefry key from a seed of up to 64 bits."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is not in [0, 2**64)")
    data = np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(data), impl="threefry2x32")


def _leaf_key(key, path: str, layer):
    k = jax.random.fold_in(key, zlib.crc32(path.encode()))
    return k if layer is None else jax.random.fold_in(k, layer)


def _exponent(fan: int) -> int:
    """Power of two that gives odd integers an RMS about 1/sqrt(fan)."""
    return round(math.log2(1.0 / math.sqrt(fan) / _ODD_RMS))


def _declared(path: str, rules: dict):
    """The rule of the longest suffix in ``rules`` that ends ``path`` at a
    "/" (or is the whole path), or None."""
    hits = [s for s in rules if path == s or path.endswith("/" + s)]
    return rules[max(hits, key=len)] if hits else None


def init_rule(path: str, shape: tuple, rules=None) -> tuple:
    """-> (kind, exponent) for a leaf of one layer (unstacked ``shape``).

    ``rules``, a reference's ``leaf_rules``, maps a path suffix
    ("moe/wi_0") to a kind and the axes whose product is the fan-in
    (``("matrix", (1,))``); it is looked up first.  Otherwise: kind
    "matrix": odd * 2**e with RMS about 1/sqrt(fan_in) (the embedding
    table: RMS about 1); "scale": 1 + odd * 2**-11; "bias": odd * 2**-11.
    An unknown path is an error: the served tree's layout has changed, or
    its reference declares no rule for it.  So is a bank of matrices
    (a 3-D ``wi_0``, ``wi_1`` or ``wo``) with no declared rule: which axis
    is the fan-in is not for this function to guess."""
    rule = _declared(path, rules or {})
    if rule is not None:
        kind, axes = rule
        if kind in ("scale", "bias"):
            return kind, -11
        if kind != "matrix":
            raise ValueError(f"rule {rule!r} for leaf {path!r} has no "
                             f"kind 'matrix', 'scale' or 'bias'")
        return "matrix", _exponent(math.prod(shape[a] for a in axes))
    name = path.rsplit("/", 1)[-1]
    if name == "scale":
        return "scale", -11
    if name == "bias":
        return "bias", -11
    if path == "embed/tokens":
        fan = 1
    elif path == "unembed/kernel":
        fan = shape[1]
    elif name in ("wi_0", "wi_1", "wo") and len(shape) > 2:
        raise ValueError(f"leaf {path!r} of shape {shape} is a bank of "
                         f"matrices: its reference has to declare its "
                         f"fan-in axes in leaf_rules")
    elif name in ("q", "k", "v", "wi_0", "wi_1", "wo"):
        fan = shape[0]
    elif name == "o":
        fan = shape[0] * shape[1]
    else:
        raise ValueError(f"no initialisation rule for leaf {path!r}")
    return "matrix", _exponent(fan)


def make_leaf(key, path: str, shape: tuple, dtype, layer=None,
              rules=None) -> jax.Array:
    """One layer's values of leaf ``path`` (``shape`` without the layer
    axis), traceable; ``rules`` as ``init_rule`` takes them."""
    kind, e = init_rule(path, shape, rules)
    n = math.prod(shape)
    # four random bytes from each 32-bit word
    words = jax.random.bits(_leaf_key(key, path, layer), (-(-n // 4),),
                            jnp.uint32)
    byte = jax.lax.bitcast_convert_type(words, jnp.uint8).reshape(-1)[:n]
    odd = byte.reshape(shape).astype(jnp.int32) * 2 - 255
    val = odd.astype(jnp.float32) * jnp.float32(2.0 ** e)
    if kind == "scale":
        val = val + 1.0
    return val.astype(dtype)


def _paths(tree) -> list:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return ["/".join(str(getattr(k, "key", k)) for k in p) for p, _ in flat]


def make_tree(abstract, seed: int, rules=None):
    """Materialise the served tree from its abstract shapes in one jitted
    call on the default device, by the reference's ``rules``.  Leaves under
    "groups" are stacked over layers (leading axis) and made one layer at a
    time (``lax.map``), so that the call holds little beyond its output."""
    leaves, treedef = jax.tree_util.tree_flatten(abstract)
    paths = _paths(abstract)

    def build(data):
        key = jax.random.wrap_key_data(data, impl="threefry2x32")
        out = []
        for path, leaf in zip(paths, leaves):
            if path.startswith("groups/"):
                layers = jnp.arange(leaf.shape[0], dtype=jnp.uint32)
                out.append(jax.lax.map(lambda i, p=path, s=leaf: make_leaf(
                    key, p, s.shape[1:], s.dtype, i, rules), layers))
            else:
                out.append(make_leaf(key, path, leaf.shape, leaf.dtype,
                                     rules=rules))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(jax.random.key_data(seed_key(seed)))
