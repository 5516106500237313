"""Operations and bytes Command A+'s served share needs, from shapes
alone (`decoder.py` says what counts), for one chip's share as the
configuration's `deployment` cuts it: the layers held, attention and
the shared experts whole, of the routed experts the share a uniform
router deals this chip.

A prefilled token passes through, a layer: the four attention
projections, the two products against the keys it sees (every key
before it on a `full_attention` layer, at most `sliding_window` on a
`sliding_attention` one; the program's masked dense blocks compute
more, which does not count), the router, the `num_shared_experts`
shared experts and this chip's share of the token's
`num_experts_per_tok` experts; and the tied head once.

A prefill from position 0 attends through the flash kernel, a call a
layer: `flash_prefill_ops_and_bytes` counts one, its bucket's padding
with it (the kernel computes those rows too).

A decode step reads every weight held once (a routed expert's only if
a pair fell on it: `touched` a layer, all of them unless given) and, a
slot, the keys and values its next token attends: the ring's on a
sliding layer, the context's on a full one.
"""

from __future__ import annotations

from benchmark.flops.decoder import least_seconds  # noqa: F401


def _kinds(config):
    return [config["layer_types"][i]
            for i in config["deployment"]["layers_held"]]


def attention_params(config):
    d, k = config["hidden_size"], config["head_dim"]
    return d * k * (2 * config["num_attention_heads"]
                    + 2 * config["num_key_value_heads"])


def expert_params(config):
    """One expert, routed or shared: three matrices."""
    return 3 * config["hidden_size"] * config["intermediate_size"]


def keys_seen(config, kind, context):
    """Keys a token with `context` keys up to itself attends on a layer
    of `kind`."""
    return min(context, config["sliding_window"]) \
        if kind == "sliding_attention" else context


def attention_flops(config, kind, context):
    """Scores and weighted sum of one token on a layer of `kind`."""
    return 2 * 2 * keys_seen(config, kind, context) \
        * config["num_attention_heads"] * config["head_dim"]


def matmul_flops_per_token(config):
    """FLOPs of a token's products with weights, over the layers held
    and the head: everything but attention's scores and sum."""
    share = config["deployment"]["experts_held"][1] \
        / config["deployment"]["router_width"]
    layer = 2 * attention_params(config) \
        + 2 * config["hidden_size"] * config["deployment"]["router_width"] \
        + 2 * expert_params(config) * (
            config["num_shared_experts"]
            + share * config["num_experts_per_tok"])
    return len(_kinds(config)) * layer \
        + 2 * config["hidden_size"] * config["vocab_size"]


def prefill_flops_per_token(config, context):
    """FLOPs of one prompt token with `context` keys before it
    (itself included), over the layers held and the head."""
    return matmul_flops_per_token(config) + sum(
        attention_flops(config, kind, context) for kind in _kinds(config))


def flash_prefill_ops_and_bytes(config, batch, rows, windowed,
                                itemsize=2):
    """(FLOPs, bytes) of one call of the flash kernel in a prefill of
    `batch` prompts of `rows` rows from position 0: the two products
    over every pair of a row and a key it sees (at most
    `sliding_window` where `windowed`; the kernel's tiles on the
    diagonal and on the window's far edge compute masked pairs besides,
    which do not count), queries and output once, keys and values
    once."""
    reach = min(rows, config["sliding_window"]) if windowed else rows
    pairs = reach * (reach + 1) // 2 + (rows - reach) * reach
    h, g, d = (config["num_attention_heads"],
               config["num_key_value_heads"], config["head_dim"])
    return (batch * 2 * 2 * pairs * h * d,
            batch * rows * d * (2 * h + 2 * g) * itemsize)


def train_flops_per_token(config, seq):
    """Forward and backward of a token at the mean context of a
    sequence of `seq`, three times the forward pass: the name every
    family's file has; this family is served, and no cell trains it."""
    return 3 * prefill_flops_per_token(config, max(1, seq // 2))


def decode_step_bytes(config, slots, context, touched=None, itemsize=2):
    """Bytes a decode step of `slots` slots has to read, each slot
    holding `context` keys: the weights once (`touched` routed experts
    a layer, every held one unless given), the keys and values each
    slot's token attends."""
    held = config["deployment"]["experts_held"][1]
    touched = held if touched is None else min(touched, held)
    row = 2 * config["num_key_value_heads"] * config["head_dim"]
    weights = config["hidden_size"] * config["vocab_size"]
    cache = 0
    for kind in _kinds(config):
        weights += attention_params(config) \
            + config["hidden_size"] * config["deployment"]["router_width"] \
            + expert_params(config) * (config["num_shared_experts"]
                                       + touched)
        cache += row * keys_seen(config, kind, context)
    return int((weights + slots * cache) * itemsize)
