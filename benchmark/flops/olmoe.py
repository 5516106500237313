"""Operations and bytes OLMoE's step needs, from shapes alone
(`decoder.py` says what counts): per token and layer the four attention
projections, the router, and the `num_experts_per_tok` experts a token
is routed to (three matrices of hidden x `intermediate_size` each), the
causal half of attention's two products, and the output head once.

A grouped matmul is one of the expert layer's products over all
(token, expert) pairs of a chip's step: pairs x hidden x width
multiply-adds whichever of the three it is, forward or backward
(y = x W, dx = dy W^T, dW = x^T dy), and it has to read or write a
[pairs, hidden] and a [pairs, width] activation and every expert's
[hidden, width] matrix once.
"""

from __future__ import annotations

from benchmark.flops.decoder import (attention_flops_per_token,  # noqa: F401
                                     flash_ops_and_bytes, least_seconds)


def matmul_params_per_token(config):
    """Weights of the matrices one token is multiplied with."""
    d = config["hidden_size"]
    h = config["num_attention_heads"]
    hkv = config["num_key_value_heads"]
    hd = d // h
    attn = d * h * hd + 2 * d * hkv * hd + h * hd * d
    experts = config["num_experts_per_tok"] * 3 * d \
        * config["intermediate_size"]
    router = d * config["num_experts"]
    return config["num_hidden_layers"] * (attn + router + experts) \
        + d * config["vocab_size"]


def train_flops_per_token(config, seq):
    return 3 * (2 * matmul_params_per_token(config)
                + attention_flops_per_token(config, seq))


def grouped_matmul_ops_and_bytes(config, tokens, itemsize=2):
    """(FLOPs, bytes) of one grouped product over the pairs of `tokens`
    tokens, for any configuration of sparse experts (Mixtral's file
    calls the number of experts `num_local_experts`)."""
    experts = config.get("num_experts") or config["num_local_experts"]
    pairs = tokens * config["num_experts_per_tok"]
    d, f = config["hidden_size"], config["intermediate_size"]
    ops = 2 * pairs * d * f
    nbytes = (pairs * d + pairs * f + experts * d * f) * itemsize
    return ops, nbytes
