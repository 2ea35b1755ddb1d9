"""Each operations/bytes function against a hand count."""

import pytest

from readers import kernel_math as km

LARGE = {"n_embd": 1280, "n_layer": 36, "n_head": 20, "vocab_size": 50257,
         "padded_vocab": 50304, "n_positions": 1024}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_matmul_params_gpt2_large():
    # per block: qkv 1280*3840 + proj 1280*1280 + fc 1280*5120 + out
    # 5120*1280 = 19,660,800; 36 blocks = 707,788,800; head 50304*1280
    assert km.matmul_params(LARGE) == 707_788_800 + 64_389_120


def test_train_flops_per_token_gpt2_large():
    # 6 * 772,177,920 = 4,633,067,520; attention 6 * 36 * 1024 * 1280
    # = 283,115,520
    assert km.train_flops_per_token(LARGE, 1024) == pytest.approx(
        4_633_067_520 + 283_115_520)


@pytest.mark.parametrize("backward,ops,nbytes", [
    # B4 T1024 E1280: 2 * 4 * 1024^2 * 1280 = 10,737,418,240 forward
    (False, 10_737_418_240, 4 * 4 * 1024 * 1280 * 2),
    (True, 26_843_545_600, 8 * 4 * 1024 * 1280 * 2),
])
def test_flash_attention_call(backward, ops, nbytes):
    assert km.flash_attention_call(4, 1024, 1280, backward) == (ops, nbytes)


def test_paged_decode_bytes():
    # 10 sequences of 300 tokens: K and V, 1280 wide, bf16
    assert km.paged_decode_bytes(3000, 1280) == 2 * 3000 * 1280 * 2


def test_roofline_takes_the_binding_bound():
    # 197e12 operations take 1 s of compute; 819e9 bytes 1 s of bandwidth
    assert km.roofline_seconds(197e12, 1.0, PEAKS) == pytest.approx(1.0)
    assert km.roofline_seconds(1.0, 2 * 819e9, PEAKS) == pytest.approx(2.0)
