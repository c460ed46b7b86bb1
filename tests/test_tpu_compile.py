"""Ahead-of-time compiles of every Pallas kernel for a described TPU v5e.

Interpret mode runs the kernel bodies on the CPU and cannot see what the
TPU compiler refuses: blocks whose last two dims break the (8, 128)
tiling, 1-D VMEM scratch, more fast memory than a kernel may use.  These
tests hand the real compiler the shapes of the main path (phi3-mini-3.8b
attention, mamba2-1.3b SSD, the Fig. 1 vector grid) against a v5e that is
described, not attached, and require a Mosaic kernel in the result.
The decode step of four architectures is compiled whole, at published
widths, to show that it updates its donated cache in place.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and each test worker imports every
test file.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import decode_attention as da
from repro.kernels import flash_attention as fa
from repro.kernels import ssd_scan as sk
from repro.kernels import vector_quantiles as vq
from repro.kernels import vector_step as vs

F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32
#: phi3-mini-3.8b attention widths (configs/phi3_mini_3_8b.py)
PHI3_H, PHI3_HD = 32, 96
#: mamba2-1.3b SSD widths: d_inner 4096 / head_dim 64 heads, d_state 128
MAMBA_H, MAMBA_P, MAMBA_N, MAMBA_CHUNK = 64, 64, 128, 256
#: the Fig. 1 grid: 117 cells padded to the kernel's cell tile
GRID_C, GRID_K = 120, 32768


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # repro: noqa[broad-except] — skip reason
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(sharding, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernel_names(text):
    """Names of the Mosaic kernels in a compiled module: what a profiler
    trace calls their ops."""
    return set(re.findall(
        r"%([A-Za-z0-9_\-]+?)(?:\.\d+)?\s= [^\n]*tpu_custom_call", text))


def test_flash_attention_phi3(one_chip):
    s = 512
    qkv = [((1, s, PHI3_H, PHI3_HD), BF16)] * 3
    text = _compiled_text(one_chip, lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True), *qkv)
    assert "tpu_custom_call" in text
    assert _kernel_names(text) == {"flash_attention"}


def test_decode_attention_phi3(one_chip):
    b, t = 4, 1024
    text = _compiled_text(
        one_chip,
        lambda q, k, v, n, kp, qp: da.decode_attention(
            q, k, v, lengths=n, key_positions=kp, q_pos=qp),
        ((b, PHI3_H, PHI3_HD), BF16), ((b, t, PHI3_H, PHI3_HD), BF16),
        ((b, t, PHI3_H, PHI3_HD), BF16), ((b,), I32), ((b, t), I32),
        ((b,), I32))
    assert "tpu_custom_call" in text
    assert _kernel_names(text) == {"decode_attention"}


def test_ssd_scan_mamba2(one_chip):
    b, s = 1, 2 * MAMBA_CHUNK
    text = _compiled_text(
        one_chip,
        lambda x, dt, a, bm, cm: sk.ssd_scan(x, dt, a, bm, cm,
                                             chunk=MAMBA_CHUNK),
        ((b, s, MAMBA_H, MAMBA_P), BF16), ((b, s, MAMBA_H), F32),
        ((MAMBA_H,), F32), ((b, s, 1, MAMBA_N), BF16),
        ((b, s, 1, MAMBA_N), BF16))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("family", ["scalar", "batched"])
@pytest.mark.parametrize("servers", [1, 64])
def test_slot_advance(one_chip, family, servers):
    cs, c = (GRID_C, servers), (GRID_C,)
    consts = {"c": (cs, F32), "fail_slot": (cs, I32), "dt": ((), F32)}
    if family == "scalar":
        fn = vs.scalar_slot_advance
        carry = [(cs, F32), (cs, F32), (c, F32)]
        xs = [((), I32), (cs, F32), (cs, F32), (c, F32), (c, F32),
              (cs, F32), (cs, F32), (cs, F32)]
    else:
        fn = vs.batched_slot_advance
        consts.update({k: ((GRID_C, 1), F32)
                       for k in ("tm", "tc", "new_mean")})
        carry = [(cs, F32)] * 3 + [(c, F32)]
        xs = [((), I32), (cs, F32), (cs, F32), (cs, F32), (c, F32),
              (c, F32), (c, F32), (cs, F32), (cs, F32), (cs, F32)]
    names = list(consts)
    n_c, n_k = len(names), len(carry)

    def step(*flat):
        return fn(dict(zip(names, flat[:n_c])), tuple(flat[n_c:n_c + n_k]),
                  tuple(flat[n_c + n_k:]), interpret=False)

    text = _compiled_text(one_chip, step, *consts.values(), *carry, *xs)
    assert "tpu_custom_call" in text


def test_fused_quantiles(one_chip):
    text = _compiled_text(one_chip, lambda lat, n: vq.fused_quantiles(lat, n),
                          ((GRID_C, GRID_K), F32), ((GRID_C,), I32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("arch", ["phi3-mini-3.8b", "gemma3-12b", "mamba2-1.3b",
                                  "whisper-small"])
def test_decode_updates_cache_in_place(one_chip, arch, monkeypatch):
    """The decode step, compiled as the engine compiles it (cache donated,
    held to its layouts at rest, the Pallas decode kernel), aliases every
    cache leaf from input to output and makes no temporary the size of the
    stacked cache: each layer writes its new token into the carried stack.
    Published widths, four layer groups, six slots of 1024 tokens."""
    import dataclasses

    from repro.configs.base import get_config
    from repro.kernels import ops
    from repro.models import param as P
    from repro.models import registry as R

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = get_config(arch)
    cfg = dataclasses.replace(cfg, num_layers=4 * len(cfg.resolved_pattern))
    b, max_len = 6, 1024

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
            P.abstract_tree(tree), is_leaf=P.is_spec)

    cache_specs = R.cache_specs(cfg, b, max_len,
                                enc_len=64 if cfg.enc_dec else None)
    cache, params = on_chip(cache_specs), on_chip(R.model_specs(cfg))
    at_rest = jax.jit(lambda c: c).lower(cache).compile().input_formats[0][0]
    layouts = jax.tree_util.tree_map(lambda f: f.layout, at_rest)
    tok = jax.ShapeDtypeStruct((b,), I32, sharding=one_chip)

    def step(c, p, t, q):
        return R.decode_step(cfg, p, c, t, q, impl="pallas",
                             cache_layouts=layouts)

    compiled = jax.jit(step, donate_argnums=0).lower(
        cache, params, tok, tok).compile()
    header = compiled.as_text().split("entry_computation_layout")[0]
    aliased = {int(m) for m in re.findall(r"\}: \((\d+), \{\}", header)}
    assert aliased == set(range(len(jax.tree_util.tree_leaves(cache))))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < P.tree_bytes(cache_specs) / 2
