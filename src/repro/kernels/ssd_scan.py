"""Mamba-2 SSD chunked-scan kernel (Pallas, TPU).

TPU adaptation of the SSD algorithm: instead of a GPU warp-level selective
scan, each chunk is a dense (L×L) decay-masked attention-like product that
runs on the MXU; the (P×N) recurrent state is carried across the innermost
(sequential) grid axis in VMEM scratch.  Grid (B, H, nChunks).

VMEM per step (L=256, P=128, N=128, fp32): x 128KB + B/C 2×128KB + M 256KB +
state 64KB ≈ 0.8 MB — comfortably resident.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32


def _kernel(a_ref, x_ref, dtc_ref, dtr_ref, b_ref, c_ref, h0_ref, y_ref,
            hN_ref, state_ref, *, chunk: int, n_chunks: int):
    hi, ci = pl.program_id(1), pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = h0_ref[...].astype(F32)

    x = x_ref[...].astype(F32)                            # (L, P)
    A = a_ref[0, hi]                                      # scalar (this head)
    dt_c = dtc_ref[...].astype(F32)                       # (L, 1)
    dt_r = dtr_ref[...].astype(F32)                       # (1, L)
    Bm = b_ref[...].astype(F32)                           # (L, N)
    Cm = c_ref[...].astype(F32)                           # (L, N)

    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = t_idx >= s_idx
    # inclusive cumsum of the log-decay a = A*dt, as a column and as a row,
    # by summing under the triangular mask
    cum = jnp.sum(jnp.where(causal, A * dt_r, 0.0), axis=1,
                  keepdims=True)                          # (L, 1)
    cum_r = jnp.sum(jnp.where(t_idx <= s_idx, A * dt_c, 0.0), axis=0,
                    keepdims=True)                        # (1, L)
    cum_last = cum[chunk - 1:, :]                         # (1, 1)
    # intra-chunk quadratic term: M[t,s] = (C_t.B_s) exp(cum_t - cum_s) dt_s, t>=s
    decay = jnp.exp(jnp.where(causal, cum - cum_r, -1e30))  # mask pre-exp
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=F32)  # (L, L)
    M = cb * decay * dt_r
    y = jax.lax.dot_general(M, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=F32)   # (L, P)
    # inter-chunk: y += exp(cum_t) * C_t . h_prev^T      (h_prev: (P, N))
    h_prev = state_ref[...]
    y += jnp.exp(cum) * jax.lax.dot_general(
        Cm, h_prev, (((1,), (1,)), ((), ())), preferred_element_type=F32)
    y_ref[...] = y.astype(y_ref.dtype)
    # state update: h = exp(cum_L) h_prev + sum_s exp(cum_L-cum_s) dt_s x_s ⊗ B_s
    w = jnp.exp(cum_last - cum) * dt_c                    # (L, 1)
    upd = jax.lax.dot_general(x, Bm * w, (((0,), (0,)), ((), ())),
                              preferred_element_type=F32)  # (P, N)
    state_ref[...] = jnp.exp(cum_last) * h_prev + upd

    @pl.when(ci == n_chunks - 1)
    def _finish():
        hN_ref[...] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, B, C, *, chunk: int = 256, h0=None,
             interpret: bool = False):
    """x: (b,s,h,p); dt: (b,s,h); A: (h,); B,C: (b,s,1,n) -> (y fp32, hN fp32).

    Runs head-major: x and dt are moved to (b, h, s, ...) so every block's
    last two dims are (chunk, p), (chunk, 1), (1, chunk) or (chunk, n)."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    if h0 is None:
        h0 = jnp.zeros((b, h, p, n), F32)
    Bs, Cs = B[:, :, 0, :], C[:, :, 0, :]                 # (b,s,n)
    dt_h = dt.astype(F32).transpose(0, 2, 1)              # (b,h,s)

    kernel = functools.partial(_kernel, chunk=chunk, n_chunks=nc)
    seq = pl.BlockSpec((None, None, chunk, p), lambda b_, h_, c_: (b_, h_, c_, 0))
    state = pl.BlockSpec((None, None, p, n), lambda b_, h_, c_: (b_, h_, 0, 0))
    bc = pl.BlockSpec((None, chunk, n), lambda b_, h_, c_: (b_, c_, 0))
    y, hN = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),        # A: (1, h)
            seq,
            pl.BlockSpec((None, None, chunk, 1), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((None, None, 1, chunk), lambda b_, h_, c_: (b_, h_, 0, c_)),
            bc, bc, state,
        ],
        out_specs=[seq, state],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, p), F32),
            jax.ShapeDtypeStruct((b, h, p, n), F32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), F32)],
        interpret=interpret,
    )(A.reshape(1, h).astype(F32), x.transpose(0, 2, 1, 3),
      dt_h[..., None], dt_h[:, :, None, :], Bs, Cs, h0)
    return y.transpose(0, 2, 1, 3), hN
