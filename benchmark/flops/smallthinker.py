"""Operations and bytes SmallThinker's step needs, from the shapes of
its file alone (`decoder.py` says what counts: forward and backward,
three times the forward pass; nothing recomputed). Per token and layer
held: the four attention projections at the file's `head_dim` (a key of
its own, not hidden over heads), the router over the published count of
experts, the experts the file holds at an even load (a token's
`moe_num_active_primary_experts` choices fall on the share held in
proportion to its size: three matrices of hidden x
`moe_ffn_hidden_size` each), and attention's two products over the
keys a row sees by the layer's kind: every key before it on a layer
whose entry of `sliding_window_layout` is 0, `sliding_window_size` at
most on a layer whose entry is 1. The output head over the vocabulary
the file holds, once.

A flash kernel's call is counted by its kind too (`window`), and a
grouped product over the pairs held at an even load: the reader sees
kernels, not the router's choices, and the step's own count
(`pairs_held` on `train.step_dispatch`, which `moe.held_pair_share.train`
reads) says how far from even a run was.
"""

from __future__ import annotations

from benchmark.flops import decoder
from benchmark.flops.decoder import least_seconds  # noqa: F401


def _published_experts(config):
    return config.get("published", {}).get(
        "moe_num_primary_experts", config["moe_num_primary_experts"])


def pairs_seen(seq, window=None):
    """(query, key) pairs of one causal head over `seq` rows: a row sees
    itself and the keys before it, `window` of them at most."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def held_pairs_per_token(config):
    """Pairs of one token and layer that fall on the experts held, at
    an even load."""
    return config["moe_num_active_primary_experts"] \
        * config["moe_num_primary_experts"] / _published_experts(config)


def matmul_params_per_token(config):
    """Weights of the matrices one token is multiplied with."""
    d, hd = config["hidden_size"], config["head_dim"]
    h, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    attn = 2 * d * h * hd + 2 * d * hkv * hd
    router = d * _published_experts(config)
    experts = held_pairs_per_token(config) * 3 * d \
        * config["moe_ffn_hidden_size"]
    return config["num_hidden_layers"] * (attn + router + experts) \
        + d * config["vocab_size"]


def attention_flops_per_token(config, seq):
    """Forward: QK^T and PV each take 2 * heads * head_dim a pair."""
    depth = config["num_hidden_layers"]
    pairs = sum(
        pairs_seen(seq, config["sliding_window_size"] if windowed else None)
        for windowed in config["sliding_window_layout"][:depth])
    return 4 * config["num_attention_heads"] * config["head_dim"] \
        * pairs / seq


def train_flops_per_token(config, seq):
    return 3 * (2 * matmul_params_per_token(config)
                + attention_flops_per_token(config, seq))


def flash_ops_and_bytes(kernel, *, batch, seq, n_heads, n_kv_heads,
                        head_dim, window=None, itemsize=2):
    """(FLOPs, bytes) of one causal call of a flash-attention kernel
    whose rows see `window` keys at most (None: every key before
    them). The bytes are `decoder.flash_ops_and_bytes`'s: q, k and v
    and what the kernel writes, each once, whatever the window."""
    _, nbytes = decoder.flash_ops_and_bytes(
        kernel, batch=batch, seq=seq, n_heads=n_heads,
        n_kv_heads=n_kv_heads, head_dim=head_dim, itemsize=itemsize)
    ops = decoder._FLASH_PRODUCTS[kernel] * 2 * batch * n_heads * head_dim \
        * pairs_seen(seq, window)
    return ops, nbytes


def grouped_matmul_ops_and_bytes(config, tokens, itemsize=2):
    """(FLOPs, bytes) of one grouped product over the pairs of `tokens`
    tokens that fall on the experts held, at an even load (98,304 of a
    step's 393,216 in the cell)."""
    pairs = round(tokens * held_pairs_per_token(config))
    d, f = config["hidden_size"], config["moe_ffn_hidden_size"]
    ops = 2 * pairs * d * f
    nbytes = (pairs * d + pairs * f
              + config["moe_num_primary_experts"] * d * f) * itemsize
    return ops, nbytes
