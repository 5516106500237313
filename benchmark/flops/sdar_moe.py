"""Operations and bytes SDAR's served stage needs, from shapes alone
(`decoder.py` says what counts): the layers held, each with its
attention and all of its experts, and the embedding and the untied
head.

A prefilled token passes through, a layer: the four attention
projections, the two products against the keys it sees (every key up to
the end of its own block of `block_length` positions), the router and
its `num_experts_per_tok` experts. A prefill computes no head: it
yields no token.

A prefill from position 0 attends through the flash kernel with the
block mask, a call a layer: `flash_prefill_ops_and_bytes` counts one,
its bucket's padding with it (the kernel computes those rows too).

A block step reads every weight held once (a routed expert's only if a
pair fell on it: `touched` a layer, all of them unless given), the head
among them, and, a slot, the keys and values its block attends, once
for the block's positions together.
"""

from __future__ import annotations

from benchmark.flops.decoder import least_seconds  # noqa: F401


def attention_params(config):
    d, k = config["hidden_size"], config["head_dim"]
    return d * k * (2 * config["num_attention_heads"]
                    + 2 * config["num_key_value_heads"])


def expert_params(config):
    """One expert: three matrices."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def keys_seen(config, context):
    """Keys a token that stands at position `context` - 1 attends: those
    up to the end of its block."""
    block = config["generation"]["block_length"]
    return -(-context // block) * block


def matmul_flops_per_token(config):
    """FLOPs of a token's products with weights over the layers held:
    everything but attention's scores and sum, and the head."""
    layer = 2 * attention_params(config) \
        + 2 * config["hidden_size"] * config["num_experts"] \
        + 2 * expert_params(config) * config["num_experts_per_tok"]
    return config["num_hidden_layers"] * layer


def head_flops(config):
    return 2 * config["hidden_size"] * config["vocab_size"]


def prefill_flops_per_token(config, context):
    """FLOPs of one prompt token with `context` keys up to itself, over
    the layers held (a prefill reads no logits)."""
    return matmul_flops_per_token(config) + config["num_hidden_layers"] \
        * 2 * 2 * keys_seen(config, context) \
        * config["num_attention_heads"] * config["head_dim"]


def flash_prefill_ops_and_bytes(config, batch, rows, windowed=False,
                                itemsize=2):
    """(FLOPs, bytes) of one call of the flash kernel in a prefill of
    `batch` prompts of `rows` rows from position 0 under the block
    mask: the two products over every pair of a row and a key it sees
    (those up to the end of its block; the kernel's tiles on the
    diagonal compute masked pairs besides, which do not count),
    queries and output once, keys and values once. No layer of this
    family has a window."""
    assert not windowed
    block = config["generation"]["block_length"]
    pairs = rows * (rows + block) // 2
    h, g, d = (config["num_attention_heads"],
               config["num_key_value_heads"], config["head_dim"])
    return (batch * 2 * 2 * pairs * h * d,
            batch * rows * d * (2 * h + 2 * g) * itemsize)


def train_flops_per_token(config, seq):
    """Forward and backward of a token at the mean context of a
    sequence of `seq`, three times the forward pass with its head: the
    name every family's file has; this family is served, and no cell
    trains it."""
    return 3 * (prefill_flops_per_token(config, max(1, seq // 2))
                + head_flops(config))


def decode_step_bytes(config, slots, context, touched=None, itemsize=2):
    """Bytes one forward of a block step of `slots` slots has to read,
    each slot holding `context` keys: the weights once (`touched`
    experts a layer, every one unless given) with the head, and the
    keys and values each slot's block attends."""
    experts = config["num_experts"]
    touched = experts if touched is None else min(touched, experts)
    row = 2 * config["num_key_value_heads"] * config["head_dim"]
    layer = attention_params(config) \
        + config["hidden_size"] * config["num_experts"] \
        + expert_params(config) * touched
    weights = config["num_hidden_layers"] * layer \
        + config["hidden_size"] * config["vocab_size"]
    cache = config["num_hidden_layers"] * row * keys_seen(config, context)
    return int((weights + slots * cache) * itemsize)
