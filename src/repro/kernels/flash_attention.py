"""FlashAttention-2-style prefill kernel (Pallas, TPU).

Grid (B, H, nQ, nKV) — KV innermost so the (m, l, acc) online-softmax state
lives in VMEM scratch across KV steps.  The kernel runs head-major: the
wrapper moves the heads axis ahead of the sequence, so every block's last
two dims are (sequence tile, head_dim) as the TPU's (8, 128) tiling
requires.  GQA is handled in the K/V BlockSpec index map
(kv_head = q_head // group).  Causal and sliding-window masks are
computed from block-local iotas; fully-masked KV blocks are skipped with
``pl.when`` (the TPU grid is sequential, so skipping saves real MXU time).

Block sizes default to (128, 512): q-tile 128×hd + kv-tile 512×hd + scratch
acc 128×hd fp32 — well under VMEM for hd ≤ 256 and MXU-aligned.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
NEG = -1e30


def fit_block(block: int, n: int) -> int:
    """``block`` where it tiles an axis of length ``n`` exactly, else the
    whole axis (a block may always span the full array dim)."""
    return block if block <= n and n % block == 0 else n


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            causal: bool, window: Optional[int], block_q: int, block_k: int,
            n_kv: int, scale: float):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k
    # skip blocks that are entirely masked out
    relevant = None
    if causal:
        relevant = k_start <= q_start + block_q - 1
    if window is not None:
        win_ok = k_start + block_k - 1 > q_start - window
        relevant = win_ok if relevant is None else jnp.logical_and(relevant, win_ok)

    def _step():
        # operands stay in their own dtype (the MXU's bf16 path, f32
        # accumulation) and the scale applies to the f32 scores, as in
        # the reference; an f32 operand would be rounded to bf16 there
        s = jax.lax.dot_general(q_ref[...], k_ref[...],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=F32) * scale  # (BQ, BK)
        qp = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kp = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        ok = jnp.ones_like(s, bool)
        if causal:
            ok &= kp <= qp
        if window is not None:
            ok &= kp > qp - window
        s = jnp.where(ok, s, NEG)
        m_prev = m_ref[...]                                # (BQ, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[...]
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=F32)
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = m_new

    if relevant is None:
        _step()
    else:
        pl.when(relevant)(_step)

    @pl.when(ki == n_kv - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None, block_q: int = 128,
                    block_k: int = 512, interpret: bool = False):
    """q: (B,S,H,hd); k,v: (B,T,KV,hd) -> (B,S,H,hd)."""
    b, s, h, hd = q.shape
    t, kv = k.shape[1], k.shape[2]
    g = h // kv
    block_q, block_k = fit_block(block_q, s), fit_block(block_k, t)
    n_q, n_kv = s // block_q, t // block_k
    grid = (b, h, n_q, n_kv)

    kernel = functools.partial(_kernel, causal=causal, window=window,
                               block_q=block_q, block_k=block_k, n_kv=n_kv,
                               scale=hd ** -0.5)
    q_spec = pl.BlockSpec((None, None, block_q, hd),
                          lambda b_, h_, qi, ki: (b_, h_, qi, 0))
    kv_spec = pl.BlockSpec((None, None, block_k, hd),
                           lambda b_, h_, qi, ki: (b_, h_ // g, ki, 0))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, s, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), F32),     # m
            pltpu.VMEM((block_q, 1), F32),     # l
            pltpu.VMEM((block_q, hd), F32),    # acc
        ],
        interpret=interpret,
        name="flash_attention",        # the op's name in a profiler trace
    )(*(a.transpose(0, 2, 1, 3) for a in (q, k, v)))
    return out.transpose(0, 2, 1, 3)
