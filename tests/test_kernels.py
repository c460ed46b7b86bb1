"""Per-kernel allclose sweeps: Pallas (interpret mode) vs pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

# JAX compile-heavy: excluded from the default suite, run with -m slow
pytestmark = pytest.mark.slow

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan

KEY = jax.random.PRNGKey(42)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,h,kv,hd,causal,window", [
    (2, 256, 4, 2, 64, True, None),
    (1, 128, 8, 8, 128, True, None),
    (2, 256, 4, 4, 64, False, None),
    (1, 256, 4, 1, 64, True, 64),
    (2, 128, 6, 2, 96, True, None),
    (1, 512, 2, 2, 128, True, 256),
    (1, 96, 4, 2, 64, True, None),       # 64 does not tile 96: one block
])
def test_flash_attention(b, s, h, kv, hd, causal, window, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, s, h, hd), dtype)
    k = jax.random.normal(ks[1], (b, s, kv, hd), dtype)
    v = jax.random.normal(ks[2], (b, s, kv, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=64, block_k=64, interpret=True)
    exp = ref.naive_attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,kv,hd,t,window", [
    (2, 4, 2, 64, 256, None),
    (4, 8, 8, 128, 512, None),
    (2, 4, 1, 64, 256, 64),
    (1, 16, 2, 96, 512, None),
    (2, 4, 2, 64, 192, None),            # 128 does not tile 192: one block
])
def test_decode_attention(b, h, kv, hd, t, window, dtype):
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (b, h, hd), dtype)
    k = jax.random.normal(ks[1], (b, t, kv, hd), dtype)
    v = jax.random.normal(ks[2], (b, t, kv, hd), dtype)
    lengths = jax.random.randint(ks[3], (b,), t // 4, t)
    out = decode_attention(q, k, v, lengths=lengths, window=window,
                           block_t=128, interpret=True)
    exp = ref.decode_attention(q, k, v, lengths=lengths, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **_tol(dtype))


def test_decode_attention_ring_positions():
    """SWA ring cache: slots carry absolute positions; window masks them."""
    b, h, kv, hd, t = 2, 4, 2, 64, 128
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (b, h, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, t, kv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, t, kv, hd), jnp.float32)
    lengths = jnp.array([200, 150])          # > t: ring wrapped
    pos = (jnp.arange(t)[None, :] + (lengths[:, None] - t))
    q_pos = lengths - 1
    out = decode_attention(q, k, v, lengths=lengths, key_positions=pos,
                           q_pos=q_pos, window=64, block_t=64, interpret=True)
    exp = ref.decode_attention(q, k, v, lengths=lengths, key_positions=pos,
                               q_pos=q_pos, window=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (2, 128, 4, 16, 16, 32),
    (1, 256, 2, 64, 128, 64),
    (2, 64, 8, 32, 64, 32),
])
def test_ssd_scan(b, s, h, p, n, chunk, dtype):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, s, h, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h), jnp.float32))
    A = -jnp.exp(jax.random.normal(ks[2], (h,), jnp.float32) * 0.5)
    B = jax.random.normal(ks[3], (b, s, 1, n), dtype)
    C = jax.random.normal(ks[4], (b, s, 1, n), dtype)
    y1, h1 = ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=True)
    y2, h2 = ref.ssd_naive(x, dt, A, B, C)
    tol = dict(rtol=3e-2, atol=3e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), **tol)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), **tol)


def test_ssd_chunked_ref_matches_naive():
    b, s, h, p, n = 2, 192, 4, 16, 32
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h), jnp.float32))
    A = -jnp.exp(jax.random.normal(ks[2], (h,), jnp.float32) * 0.5)
    B = jax.random.normal(ks[3], (b, s, 1, n), jnp.float32)
    C = jax.random.normal(ks[4], (b, s, 1, n), jnp.float32)
    y1, h1 = ref.ssd_chunked(x, dt, A, B, C, chunk=64)
    y2, h2 = ref.ssd_naive(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), rtol=2e-4, atol=2e-4)


def test_ssd_state_continuation():
    """h0 chaining: scan(first half) -> scan(second half) == scan(full)."""
    b, s, h, p, n = 1, 128, 2, 16, 16
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h), jnp.float32))
    A = -jnp.exp(jax.random.normal(ks[2], (h,), jnp.float32) * 0.5)
    B = jax.random.normal(ks[3], (b, s, 1, n), jnp.float32)
    C = jax.random.normal(ks[4], (b, s, 1, n), jnp.float32)
    y_full, _ = ref.ssd_naive(x, dt, A, B, C)
    ya, ha = ssd_scan(x[:, :64], dt[:, :64], A, B[:, :64], C[:, :64],
                      chunk=32, interpret=True)
    yb, _ = ssd_scan(x[:, 64:], dt[:, 64:], A, B[:, 64:], C[:, 64:],
                     chunk=32, h0=ha, interpret=True)
    y = jnp.concatenate([ya, yb], axis=1)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_full), rtol=2e-4, atol=2e-4)


def test_chunked_attention_matches_naive():
    b, s, h, kv, hd = 2, 512, 4, 2, 64
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, s, h, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kv, hd), jnp.float32)
    out = ref.chunked_attention(q, k, v, causal=True, chunk=128)
    exp = ref.naive_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=2e-5, atol=2e-5)
