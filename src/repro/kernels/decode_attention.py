"""Flash-decode kernel: one query token vs a long KV cache (Pallas, TPU).

Grid (B, KV, nT) — KV-sequence blocks innermost; online-softmax state in
VMEM scratch.  The kernel runs head-major: the wrapper moves the KV-heads
axis of the cache ahead of the sequence, so every block's last two dims
are (sequence tile, head_dim) as the TPU's (8, 128) tiling requires.  The
GQA q-head group (G = H/KV rows) rides the MXU M dimension.
Per-sequence cache lengths, per-slot absolute key positions (ring
buffers for SWA layers), and the query position arrive as scalar /
position inputs so ragged batches mask correctly.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import fit_block

F32 = jnp.float32
NEG = -1e30


def _kernel(meta_ref, q_ref, k_ref, v_ref, kp_ref, o_ref, m_ref, l_ref,
            acc_ref, *, block_t: int, n_t: int, window: Optional[int],
            scale: float):
    b, ti = pl.program_id(0), pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # operands in their own dtype, f32 accumulation, scale on the f32
    # scores: the reference's rounding points (see flash_attention)
    s = jax.lax.dot_general(q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
                            preferred_element_type=F32) * scale  # (G, BT)
    kp = kp_ref[...]                                       # (1, BT) abs positions
    length = meta_ref[b, 0]
    ok = (kp < length) & (kp >= 0)
    if window is not None:
        q_pos = meta_ref[b, 1]
        ok &= kp > q_pos - window
    s = jnp.where(ok, s, NEG)
    m_prev = m_ref[...]                                    # (G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    v = v_ref[...]
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=F32)
    m_ref[...] = m_new

    @pl.when(ti == n_t - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "block_t", "interpret"))
def decode_attention(q, k, v, *, lengths, key_positions=None, q_pos=None,
                     window: Optional[int] = None, block_t: int = 512,
                     interpret: bool = False):
    """q: (B,H,hd); k,v: (B,T,KV,hd); lengths: (B,) -> (B,H,hd)."""
    b, h, hd = q.shape
    t, kv = k.shape[1], k.shape[2]
    g = h // kv
    block_t = fit_block(block_t, t)
    n_t = t // block_t
    if key_positions is None:
        key_positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    if q_pos is None:
        q_pos = jnp.maximum(lengths - 1, 0)
    meta = jnp.stack([lengths.astype(jnp.int32), q_pos.astype(jnp.int32)], axis=1)
    qg = q.reshape(b, kv, g, hd)
    # (B, 1, T): the position block's last two dims are (1, block_t)
    kpos = key_positions.astype(jnp.int32).reshape(b, 1, t)

    kernel = functools.partial(_kernel, block_t=block_t, n_t=n_t,
                               window=window, scale=hd ** -0.5)
    q_spec = pl.BlockSpec((None, None, g, hd),
                          lambda b_, k_, ti, meta: (b_, k_, 0, 0))
    kv_spec = pl.BlockSpec((None, None, block_t, hd),
                           lambda b_, k_, ti, meta: (b_, k_, ti, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kv, n_t),
        in_specs=[q_spec, kv_spec, kv_spec,
                  pl.BlockSpec((None, 1, block_t),
                               lambda b_, k_, ti, meta: (b_, 0, ti))],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((g, 1), F32),      # m
            pltpu.VMEM((g, 1), F32),      # l
            pltpu.VMEM((g, hd), F32),     # acc
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, g, hd), q.dtype),
        interpret=interpret,
        name="decode_attention",        # the op's name in a profiler trace
    )(meta, qg, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), kpos)
    return out.reshape(b, h, hd)
