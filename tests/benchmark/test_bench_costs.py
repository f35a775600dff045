"""Operation and byte counts against hand-worked shapes; the peaks table."""
import pytest

from benchmark import costs, manifest, peaks

M = {"hidden_size": 8, "intermediate_size": 16, "head_dim": 4,
     "num_attention_heads": 2, "num_key_value_heads": 1, "vocab_size": 32,
     "num_hidden_layers": 3, "tie_word_embeddings": False}


def test_parameter_counts_by_hand():
    # wq 8x8, wk 8x4, wv 8x4, wo 8x8, three 8x16 FFN matrices
    assert costs.layer_matmul_params(M) == 64 + 32 + 32 + 64 + 3 * 128
    assert costs.matmul_params(M) == 3 * 576 + 8 * 32
    assert costs.total_params(M) == 3 * (576 + 16) + 2 * 256 + 8


def test_mistral_counts_match_the_published_model():
    man = manifest.Manifest()
    full = dict(man.config("mistral-7b-v0.3-serve"), num_hidden_layers=32)
    assert costs.total_params(full) == pytest.approx(7.248e9, rel=1e-3)
    train = man.config("mistral-7b-v0.3-train-mesh4")
    assert costs.total_params(train) == pytest.approx(2.886e9, rel=1e-3)
    # 6 per matmul parameter and the causal half of attention; below the
    # program's 6N + 12LhS (19.7e9), which counts the gather and the square
    n = costs.total_params(train) - 32768 * 4096 - 25 * 4096
    assert costs.train_flops_per_token(train, 4096) == pytest.approx(
        6 * n + 6 * 12 * 4096 * 4097, rel=1e-9)
    assert costs.train_flops_per_token(train, 4096) < 19.7e9
    assert costs.kv_bytes_per_token(man.config("mistral-7b-v0.3-serve")) \
        == 2 * 16 * 8 * 128 * 2


def test_attention_counts_the_causal_half():
    # 3 queries, no history: 1 + 2 + 3 = 6 (query, key) pairs; 2 matmuls of
    # 2 FLOPs a multiply-add over width 8, in 3 layers
    assert costs.attn_flops_causal(M, 3) == 3 * 4 * 8 * 6
    # 2 queries after 5 earlier positions: 6 + 7 pairs
    assert costs.attn_flops_causal(M, 2, 5) == 3 * 4 * 8 * 13


def test_decode_step_reads_weights_once_and_live_kv():
    flops, nbytes = costs.decode_step_cost(M, slots=2, live_tokens=10)
    n = costs.matmul_params(M)
    assert flops == 2 * n * 2 + 4 * 3 * 2 * 4 * 10
    assert nbytes == n * 2 + (2 * 3 * 1 * 4 * 2) * (10 + 2)
    f2, b2 = costs.decode_attention_cost(M, 2, 10)
    assert f2 == 4 * 3 * 2 * 4 * 10 and b2 == 48 * 10


def test_prefill_and_flash_counts():
    assert costs.prefill_flops(M, 4) == (
        2 * 3 * 576 * 4 + costs.attn_flops_causal(M, 4) + 2 * 8 * 32)
    f, b = costs.flash_cost(M, [4, 2])
    assert f == costs.attn_flops_causal(M, 4) + costs.attn_flops_causal(M, 2)
    assert b == (2 * 2 + 2 * 1) * 4 * 2 * 3 * 6
    fb, bb = costs.flash_cost(M, [4, 2], backward=True)
    assert fb == 3 * f and bb == 3 * b


def test_roofline_share_says_which_peak_bounds():
    pk = peaks.peak("TPU v5 lite")
    assert (pk.flops, pk.hbm_bw) == (197e12, 8.19e11)
    share, bound = costs.roofline_share(197e12, 0.0, 2.0, pk)
    assert (share, bound) == (50.0, "flops")
    share, bound = costs.roofline_share(1.0, 8.19e11, 4.0, pk)
    assert (share, bound) == (25.0, "bytes")
    with pytest.raises(KeyError):
        peaks.peak("TPU v9")
