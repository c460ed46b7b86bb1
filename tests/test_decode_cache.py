"""What one decode step writes into the stacked cache.

Each attention layer of each group writes exactly one K/V row and one
position per batch slot, at ``positions % size`` (a ring for sliding-window
layers), and leaves every other element bit-equal: a stray or missing
write that greedy tokens can miss shows here.  Cross-attention K/V are
never written.  Runs on the CPU at smoke widths, through the CPU's write
(one per layer); the row-by-row write the TPU compiles is held bit-equal
to it.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MAMBA, get_config
from repro.models import attention as A
from repro.models import param as P
from repro.models import registry as R

B, MAX_LEN, GROUPS = 4, 64, 3
#: per slot: a fresh slot, one past the window (16 at smoke widths), a
#: ring that has wrapped twice, and the last position of ``MAX_LEN``
POSITIONS = np.array([0, 16, 37, MAX_LEN - 1], np.int32)


def _config(arch):
    cfg = get_config(arch + "-smoke")
    return dataclasses.replace(
        cfg, num_layers=GROUPS * len(cfg.resolved_pattern))


def _held_positions(size):
    """(B, size): the absolute position each slot holds before the step,
    the newest one below the slot's position that lands there, else -1."""
    t = np.arange(size)[None, :]
    last = POSITIONS[:, None] - 1
    held = t + size * np.floor_divide(last - t, size)
    return np.where(last >= t, held, -1).astype(np.int32)


def _random_cache(cfg, key):
    """Cache leaves full of noise, positions consistent with ``POSITIONS``."""
    enc_len = 8 if cfg.enc_dec else None
    specs = R.cache_specs(cfg, B, MAX_LEN, enc_len=enc_len)
    flat, tree = jax.tree_util.tree_flatten_with_path(specs, is_leaf=P.is_spec)
    keys = jax.random.split(key, len(flat))
    leaves = []
    for (path, s), k in zip(flat, keys):
        if path[-1].key == "pos":
            held = _held_positions(s.shape[-1])
            leaves.append(jnp.asarray(np.broadcast_to(held, s.shape)))
        else:
            leaves.append(jax.random.normal(k, s.shape, jnp.float32)
                          .astype(s.dtype))
    return jax.tree_util.tree_unflatten(tree, leaves)


def _bits(x):
    x = np.asarray(x)
    return x.view(np.uint16) if x.dtype.itemsize == 2 else x


@pytest.mark.parametrize("arch", ["phi3-mini-3.8b", "gemma3-12b",
                                  "whisper-small", "jamba-1.5-large-398b"])
def test_one_row_per_slot_per_layer(arch):
    cfg = _config(arch)
    key_p, key_c, key_t = jax.random.split(jax.random.PRNGKey(3), 3)
    params = R.init_params(cfg, key_p)
    cache = _random_cache(cfg, key_c)
    before = jax.tree_util.tree_map(np.asarray, cache)
    tokens = jax.random.randint(key_t, (B,), 0, cfg.vocab_size)
    layouts = R.decode_layouts(cache)
    step = jax.jit(lambda c, p, t, q: R.decode_step(cfg, p, c, t, q,
                                                    cache_layouts=layouts),
                   donate_argnums=0)
    _, after = step(cache, params, tokens, jnp.asarray(POSITIONS))

    n_checked = 0
    for i, kind in enumerate(cfg.resolved_pattern):
        old, new = before[f"pos{i}"], after[f"pos{i}"]
        for name in ("ek", "ev"):
            if name in old:
                np.testing.assert_array_equal(_bits(new[name]), _bits(old[name]))
        if kind == MAMBA:
            continue
        size = old["pos"].shape[-1]
        slot = POSITIONS % size
        written = np.zeros(old["pos"].shape, bool)        # (G, B, size)
        written[:, np.arange(B), slot] = True
        np.testing.assert_array_equal(
            np.asarray(new["pos"])[written],
            np.tile(POSITIONS, GROUPS))
        for name in ("k", "v", "pos"):
            o, n = _bits(old[name]), _bits(new[name])
            changed = o != n
            if changed.ndim > 3:                          # (G, B, size, KV, hd)
                changed = changed.any(axis=(-2, -1))
            assert not (changed & ~written).any(), \
                f"pos{i}.{name}: written outside the new token's rows"
            assert changed[written].all(), \
                f"pos{i}.{name}: a new token's row was not written"
            n_checked += 1
    assert n_checked > 0


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int32])
@pytest.mark.parametrize("shape", [(GROUPS, B, MAX_LEN, 2, 8),   # K/V
                                   (GROUPS, B, 16)])             # ring pos
def test_row_writer_matches_layer_writer(shape, dtype):
    """The TPU's writer (one dynamic-update-slice per slot) and the CPU's
    (one write of the whole layer) leave the same stack and slice."""
    k_leaf, k_rows = jax.random.split(jax.random.PRNGKey(5))
    leaf = (jax.random.normal(k_leaf, shape) * 100).astype(dtype)
    rows = (jax.random.normal(k_rows, (B,) + shape[3:]) * 100).astype(dtype)
    slot = jnp.asarray(POSITIONS % shape[2])
    for layer in range(GROUPS):
        got = jax.jit(A._write_rows)(leaf, rows, layer, slot)
        want = jax.jit(A._write_layer)(leaf, rows, layer, slot)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(_bits(g), _bits(w))
