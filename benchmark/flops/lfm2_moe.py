"""Operations and bytes LFM2's served stage needs, from shapes alone
(`decoder.py` says what counts): the layers held (`deployment.
layers_held` of the published `layer_types`), each a conv or a full
mixer over the dense SwiGLU (the layers below `num_dense_layers`) or
the experts, and the tied embedding as the head.

A token passes through, a layer: a conv mixer's two projections (d x 3d
in, d x d out) and, elementwise, its two gates and the `conv_L_cache`
taps of the convolution; or a full mixer's four projections at heads
of `head_dim` 64 and the two products against the keys it sees; then
the dense SwiGLU's three matrices, or the router and
`num_experts_per_tok` experts of three matrices each.

A decode step reads every matrix held once (a routed expert's only if a
pair fell on it: `touched` a layer, all of them unless given: at 64
slots 256 pairs fall on 32 experts), the head among them, and, a slot,
the keys and values of its context in the full layers and the two
carried rows of every conv layer, read and written.

A prefill from position 0 attends through the flash kernel, a call a
full layer: `flash_prefill_ops_and_bytes` counts one, its bucket's
padding with it (the kernel computes those rows too).
"""

from __future__ import annotations

from benchmark.flops.decoder import least_seconds  # noqa: F401


def layers(config):
    """{"conv", "full", "dense", "sparse"}: how many of the layers held
    have each mixer and each FFN."""
    held = config["deployment"]["layers_held"]
    conv = sum(config["layer_types"][i] == "conv" for i in held)
    dense = sum(i < config["num_dense_layers"] for i in held)
    return {"conv": conv, "full": len(held) - conv, "dense": dense,
            "sparse": len(held) - dense}


def conv_params(config):
    """A conv mixer's two matrices."""
    return 4 * config["hidden_size"] ** 2


def conv_elementwise_flops(config):
    """A token through a conv mixer's gates and taps: B * u, C * c, and
    a multiply and an add a tap."""
    return (2 + 2 * config["conv_L_cache"]) * config["hidden_size"]


def attention_params(config):
    d, k = config["hidden_size"], config["head_dim"]
    return d * k * (2 * config["num_attention_heads"]
                    + 2 * config["num_key_value_heads"])


def dense_params(config):
    return 3 * config["hidden_size"] * config["intermediate_size"]


def expert_params(config):
    """One expert: three matrices."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def router_params(config):
    return config["hidden_size"] * config["num_experts"]


def matmul_flops_per_token(config):
    """FLOPs of a token's products with weights over the layers held:
    everything but attention's scores and sum, the convolution's
    elementwise part, and the head."""
    n = layers(config)
    return 2 * (n["conv"] * conv_params(config)
                + n["full"] * attention_params(config)
                + n["dense"] * dense_params(config)
                + n["sparse"] * (router_params(config) + expert_params(config)
                                 * config["num_experts_per_tok"]))


def head_flops(config):
    return 2 * config["hidden_size"] * config["vocab_size"]


def prefill_flops_per_token(config, context):
    """FLOPs of one prompt token with `context` keys up to itself, over
    the layers held (a prefill's logits are one row, not counted)."""
    n = layers(config)
    return matmul_flops_per_token(config) \
        + n["conv"] * conv_elementwise_flops(config) \
        + n["full"] * 2 * 2 * context * config["num_attention_heads"] \
        * config["head_dim"]


def flash_prefill_ops_and_bytes(config, batch, rows, windowed=False,
                                itemsize=2):
    """(FLOPs, bytes) of one call of the flash kernel in a prefill of
    `batch` prompts of `rows` rows from position 0: the two products
    over every pair of a row and a key it sees (the kernel's tiles on
    the diagonal compute masked pairs besides, which do not count),
    queries and output once, keys and values once. No layer of this
    family has a window."""
    assert not windowed
    pairs = rows * (rows + 1) // 2
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


def kv_bytes_per_token(config, itemsize=2):
    """Keys and values of one token in one full layer."""
    return 2 * config["num_key_value_heads"] * config["head_dim"] * itemsize


def state_bytes_per_slot(config, itemsize=2):
    """The carried rows of one slot in one conv layer."""
    return (config["conv_L_cache"] - 1) * config["hidden_size"] * itemsize


def decode_step_bytes(config, slots, context, touched=None, itemsize=2):
    """Bytes a decode step of `slots` slots has to move, each slot
    holding `context` keys: the weights once (`touched` experts a
    sparse layer, every one unless given) with the tied head, the keys
    and values each slot attends, and every conv layer's carried rows
    in and out."""
    n = layers(config)
    experts = config["num_experts"]
    touched = experts if touched is None else min(touched, experts)
    weights = n["conv"] * (conv_params(config)
                           + config["conv_L_cache"] * config["hidden_size"]) \
        + n["full"] * attention_params(config) \
        + n["dense"] * dense_params(config) \
        + n["sparse"] * (router_params(config)
                         + expert_params(config) * touched) \
        + config["hidden_size"] * config["vocab_size"]
    return int(weights * itemsize + slots * (
        n["full"] * kv_bytes_per_token(config, itemsize) * context
        + n["conv"] * 2 * state_bytes_per_slot(config, itemsize)))
