"""The functions that compute operations and bytes, against values
worked by hand, and the table of peaks."""

import pytest

from benchmark.flops import decoder
from benchmark.harness import device as hw
from benchmark.harness.manifest import BENCH_DIR, load_json

MISTRAL = {"hidden_size": 4096, "num_attention_heads": 32,
           "num_key_value_heads": 8, "intermediate_size": 14336,
           "num_hidden_layers": 4, "vocab_size": 32768}
MIXTRAL = {**MISTRAL, "num_hidden_layers": 2, "vocab_size": 32000,
           "num_local_experts": 8, "num_experts_per_tok": 2}


def test_dense_matmul_params_by_hand():
    # A layer: wq 4096x4096, wk and wv 4096x1024 each, wo 4096x4096,
    # three 4096x14336 feed-forward matrices.
    layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert layer == 218_103_808
    assert decoder.matmul_params_per_token(MISTRAL) == \
        4 * layer + 4096 * 32768 == 1_006_632_960


def test_sparse_counts_only_the_experts_a_token_uses():
    attn = 2 * 4096 * 4096 + 2 * 4096 * 1024
    layer = attn + 2 * 3 * 4096 * 14336 + 4096 * 8  # two experts, router
    assert decoder.matmul_params_per_token(MIXTRAL) == \
        2 * layer + 4096 * 32000


def test_train_flops_per_token_by_hand():
    # Forward: 2 per weight, plus causal attention 2 * seq * d a layer;
    # backward twice that.
    fwd = 2 * 1_006_632_960 + 4 * 2 * 4096 * 4096
    assert decoder.train_flops_per_token(MISTRAL, 4096) == 3 * fwd
    assert decoder.train_flops_per_token(MISTRAL, 4096) == 6_442_450_944


@pytest.mark.parametrize("kernel,products,q_like,k_like,rows", [
    ("flash_fwd", 2, 2, 2, 1),
    ("flash_bwd_dq", 3, 3, 2, 2),
    ("flash_bwd_dkv", 4, 2, 4, 2),
])
def test_flash_ops_and_bytes_by_hand(kernel, products, q_like, k_like, rows):
    ops, nbytes = decoder.flash_ops_and_bytes(
        kernel, batch=2, seq=4096, n_heads=32, n_kv_heads=8, head_dim=128)
    # One product: 2 * b * h * s^2 * hd, halved by the causal mask.
    assert ops == products * 2 * 2 * 32 * 4096 * 4096 * 128 // 2
    q = 2 * 4096 * 32 * 128 * 2
    k = 2 * 4096 * 8 * 128 * 2
    assert nbytes == q_like * q + k_like * k + rows * 2 * 32 * 4096 * 4
    full, _ = decoder.flash_ops_and_bytes(
        kernel, batch=2, seq=4096, n_heads=32, n_kv_heads=8, head_dim=128,
        causal=False)
    assert full == 2 * ops


def test_roofline_names_its_bound():
    peaks = hw.peaks("TPU v5 lite")
    ops, nbytes = decoder.flash_ops_and_bytes(
        "flash_fwd", batch=2, seq=4096, n_heads=32, n_kv_heads=8,
        head_dim=128)
    t, bound = decoder.least_seconds(ops, nbytes, peaks)
    assert bound == "compute" and t == pytest.approx(ops / 197e12)
    t, bound = decoder.least_seconds(1e6, 819e9, peaks)
    assert bound == "memory" and t == pytest.approx(1.0)


def test_peaks_table():
    table = load_json(BENCH_DIR, "peaks.json")
    assert table["source"]
    v5e = hw.peaks("TPU v5 lite")
    assert v5e == hw.peaks("TPU v5e")
    assert (v5e["bf16_flops_per_s"], v5e["hbm_bytes_per_s"]) == \
        (197e12, 819e9)
    with pytest.raises(SystemExit, match="no peaks for device_kind"):
        hw.peaks("TPU v9 imaginary")
