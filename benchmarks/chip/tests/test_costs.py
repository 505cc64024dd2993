"""Required FLOPs and bytes against hand counts, and the peak table."""
import pytest

from chiplib import costs, peaks

M = {"hidden_size": 4096, "intermediate_size": 14336,
     "num_attention_heads": 32, "num_key_value_heads": 8, "head_dim": 128,
     "vocab_size": 32768}


def test_matmul_params_by_hand():
    per_layer = (4096 * 6144) + (4096 * 4096) + 3 * (4096 * 14336)
    assert per_layer == 218_103_808
    assert costs.matmul_params(M, 2) == 2 * per_layer + 4096 * 32768
    assert costs.total_params(M, 2) == (2 * per_layer + 2 * 4096 * 32768
                                        + 5 * 4096)


def test_train_flops_per_token_by_hand():
    # forward: 2 FLOPs a weight, plus causal attention: 2 matmuls x
    # 2 FLOPs x 32 heads x 128 x (s+1)/2 visible keys a token, per layer
    s, layers = 4096, 2
    fwd = 2 * costs.matmul_params(M, layers) \
        + layers * 2 * 2 * 32 * 128 * (s + 1) / 2
    assert costs.train_flops_per_token(M, layers, s) == pytest.approx(
        3 * fwd)
    # the embedding gather is not a matmul, attention is causal: less
    # than the program's own 6 * (params + 2 * L * h * s) count
    over = 6 * (costs.matmul_params(M, layers) + 4096 * 32768
                + layers * 2 * 4096 * s)
    assert costs.train_flops_per_token(M, layers, s) < over


def test_flash_counts_by_hand():
    s, b = 4096, 2
    pairs = s * (s + 1) / 2
    assert costs.flash_fwd_bwd_flops(M, s, b) == b * 32 * 128 * pairs * 12
    q, kv = b * s * 32 * 128 * 2, b * s * 8 * 128 * 2
    assert costs.flash_fwd_bwd_bytes(M, s, b) == 6 * q + 6 * kv


def test_decode_bytes_by_hand():
    assert costs.kv_bytes_per_token(M, 8) == 2 * 8 * 8 * 128 * 2 == 32768
    assert costs.weight_bytes(M, 8) == 2 * costs.matmul_params(M, 8)
    assert costs.decode_round_bytes(M, 8, 1000) == \
        costs.weight_bytes(M, 8) + 1000 * 32768


def test_peaks_keyed_by_device_kind_and_unknown_is_an_error():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
