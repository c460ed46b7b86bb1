"""Operation and byte counts against hand-worked shapes.  Counts take true
lengths only: a prompt padded to its bucket and a slot's unused cache add
nothing."""
import pytest

from chipbench import counts as C

# phi3-mini-3.8b, by hand
M = {"layers": 32, "d": 3072, "heads": 32, "kv_heads": 32, "head_dim": 96,
     "d_ff": 8192, "vocab": 32064}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_layer_params_by_hand():
    # q, k, v, o: 4 * 3072 * 3072; SwiGLU: 3 * 3072 * 8192
    assert C.layer_params(M) == 4 * 3072 ** 2 + 3 * 3072 * 8192 == 113_246_208


def test_flash_attention_counts_by_hand():
    f, b = C.flash_attention(M, 3)
    # 3 tokens: 1 + 2 + 3 = 6 causal pairs, 4 * heads * hd flops each
    assert f == 32 * 4 * 32 * 96 * 6
    # q, k, v, o, each 3 * 32 * 96 bf16 values, per layer
    assert b == 32 * 4 * 3 * 32 * 96 * 2


@pytest.mark.parametrize("prompt,bucket", [(700, 1024), (1025, 2048),
                                           (64, 64)])
def test_padded_prefill_counts_as_unpadded(prompt, bucket):
    # the count is a function of the prompt alone: the bucket never enters
    assert C.prefill_flops(M, prompt) == (
        2 * prompt * 32 * C.layer_params(M) + C.flash_attention(M, prompt)[0]
        + 2 * 32064 * 3072)
    assert C.prefill_flops(M, prompt) < C.prefill_flops(M, bucket) or \
        prompt == bucket


def test_part_filled_cache_counts_its_context_only():
    # two slots at contexts 100 and 300 of a 1440-long cache
    f, b = C.decode_attention(M, [100, 300])
    assert f == 32 * 4 * 32 * 96 * 400
    assert b == 32 * (2 * (2 * 32 * 96 * 400 + 2 * 2 * 32 * 96)
                      + 4 * 400)
    # the same contexts, whatever the cache's length or the idle slots
    assert C.decode_attention(M, [300, 100]) == (f, b)
    assert C.decode_flops(M, [100, 300]) == (
        2 * (2 * 32 * C.layer_params(M) + 2 * 32064 * 3072) + f)


def test_roofline_bound():
    t, bound = C.roofline_seconds(197e12, 1.0, PEAK)
    assert (t, bound) == (1.0, "compute")
    t, bound = C.roofline_seconds(1.0, 819e9, PEAK)
    assert (t, bound) == (1.0, "hbm")
