"""Per-architecture smoke tests (reduced configs) + serving equivalences."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# JAX compile-heavy: excluded from the default suite, run with -m slow
pytestmark = pytest.mark.slow

from repro.configs.base import get_config, list_configs, shapes_for
from repro.models import registry as R

KEY = jax.random.PRNGKey(7)
ALL_ARCHS = list_configs()


def _smoke_batch(cfg, B=2, S=64, with_targets=False, key=KEY):
    ks = jax.random.split(key, 3)
    batch = {"tokens": jax.random.randint(ks[0], (B, S), 0, cfg.vocab_size)}
    n_extra = 0
    if cfg.embed_frontend == "patch":
        batch["tokens"] = batch["tokens"][:, : S - 16]
        batch["patch_embeds"] = jax.random.normal(ks[1], (B, 16, 1024), jnp.float32)
        n_extra = 16
    if cfg.enc_dec:
        batch["frames"] = jax.random.normal(ks[2], (B, 32, 128), jnp.float32)
    if with_targets:
        tg = jax.random.randint(ks[1], (B, S), 0, cfg.vocab_size)
        if cfg.embed_frontend == "patch":
            tg = tg.at[:, :n_extra].set(-1)   # image prefix masked
        batch["targets"] = tg
    return batch


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_forward_shapes_finite(arch):
    cfg = get_config(arch + "-smoke")
    params = R.init_params(cfg, KEY)
    batch = _smoke_batch(cfg)
    logits = R.lm_logits(cfg, params, batch)
    S = 64 if not cfg.embed_frontend == "patch" else 64
    assert logits.shape[0] == 2 and logits.shape[-1] == cfg.vocab_size
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_train_step_smoke(arch):
    """One train step on CPU: loss finite, params update, no NaNs."""
    from repro.training.optimizer import OptConfig, init_opt_state
    from repro.training.train_step import make_train_step

    cfg = get_config(arch + "-smoke")
    params = R.init_params(cfg, KEY)
    opt_cfg = OptConfig(warmup_steps=1, total_steps=10)
    opt = init_opt_state(params, opt_cfg)
    step = jax.jit(make_train_step(cfg, opt_cfg))
    batch = _smoke_batch(cfg, with_targets=True)
    new_params, new_opt, metrics = step(params, opt, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert int(new_opt["step"]) == 1
    # at least one param changed
    changed = any(
        not np.array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
        for a, b in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(new_params)))
    assert changed
    for leaf in jax.tree_util.tree_leaves(new_params):
        assert bool(jnp.isfinite(leaf.astype(jnp.float32)).all())


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_prefill_matches_forward(arch):
    cfg = get_config(arch + "-smoke")
    params = R.init_params(cfg, KEY)
    batch = _smoke_batch(cfg)
    logits_p, cache, pos = R.prefill(cfg, params, batch, max_len=96)
    logits_f = R.lm_logits(cfg, params, batch)[:, -1]
    np.testing.assert_allclose(np.asarray(logits_p, np.float32),
                               np.asarray(logits_f, np.float32),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("arch", ["phi3-mini-3.8b", "gemma3-12b", "mamba2-1.3b",
                                  "whisper-small", "command-r-35b",
                                  "llava-next-mistral-7b", "stablelm-3b"])
def test_decode_matches_forward(arch):
    """Greedy decode continuation == full forward at each step (non-MoE:
    MoE capacity drops make train/decode differ by design)."""
    cfg = get_config(arch + "-smoke")
    params = R.init_params(cfg, KEY)
    B, S, STEPS = 2, 48, 3
    toks = jax.random.randint(KEY, (B, S + STEPS), 0, cfg.vocab_size)
    batch = _smoke_batch(cfg, S=S)
    batch["tokens"] = toks[:, :S] if cfg.embed_frontend != "patch" else toks[:, : S - 16]
    logits, cache, pos = R.prefill(cfg, params, batch, max_len=S + STEPS + 8)
    for i in range(STEPS):
        tok = toks[:, S + i]
        logits, cache = R.decode_step(cfg, params, cache, tok, pos,
                                      cache_layouts=R.decode_layouts(cache))
        pos = pos + 1
        fb = dict(batch)
        fb["tokens"] = jnp.concatenate([batch["tokens"], toks[:, S:S + i + 1]], 1)
        full = R.lm_logits(cfg, params, fb)[:, -1]
        tol = 8e-2 if cfg.mamba is not None else 2e-2   # bf16 SSD state drift
        np.testing.assert_allclose(np.asarray(logits, np.float32),
                                   np.asarray(full, np.float32),
                                   rtol=tol, atol=tol)


def test_moe_dispatch_matches_dense_generous_capacity():
    cfg = get_config("deepseek-moe-16b-smoke")
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    params = R.init_params(cfg, KEY)
    batch = _smoke_batch(cfg)
    a = R.lm_logits(cfg, params, batch, moe_impl="dispatch")
    b = R.lm_logits(cfg, params, batch, moe_impl="dense")
    np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_param_counts_full_configs():
    """Full configs match their nameplate sizes (sanity on the specs)."""
    expect = {
        "llava-next-mistral-7b": (7.0e9, 7.6e9),
        "stablelm-3b": (2.5e9, 3.2e9),
        "gemma3-12b": (10e9, 13.5e9),
        "phi3-mini-3.8b": (3.4e9, 4.0e9),
        "command-r-35b": (28e9, 37e9),
        "mixtral-8x22b": (130e9, 145e9),
        "deepseek-moe-16b": (14e9, 18e9),
        "jamba-1.5-large-398b": (370e9, 420e9),
        "mamba2-1.3b": (1.1e9, 1.5e9),
        "whisper-small": (0.2e9, 0.3e9),
    }
    for arch, (lo, hi) in expect.items():
        n = R.count_params(get_config(arch))
        assert lo <= n <= hi, f"{arch}: {n/1e9:.2f}B not in [{lo/1e9}, {hi/1e9}]"


def test_active_params_moe():
    n_all = R.count_params(get_config("mixtral-8x22b"))
    n_act = R.count_params(get_config("mixtral-8x22b"), active=True)
    assert n_act < n_all / 2.2          # top-2 of 8 experts + dense part
    assert 35e9 < n_act < 45e9          # ~39B active for 8x22


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_shape_cells_defined(arch):
    cfg = get_config(arch)
    cells = shapes_for(cfg)
    names = {c.name for c in cells}
    assert {"train_4k", "prefill_32k", "decode_32k"} <= names
    if cfg.sub_quadratic:
        assert "long_500k" in names
