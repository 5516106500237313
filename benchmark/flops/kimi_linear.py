"""Operations and bytes Kimi Linear's served share needs, from shapes
alone (`decoder.py` says what counts), for one chip's share as the
configuration's `deployment` cuts it: the layers held
(`deployment.layers_held`, counted from 0, of the published
`linear_attn_config`'s lists, counted from 1), the mixers and the
shared expert whole, of the routed experts those held. Nothing here
knows the program's chunk or sub-block or how it lays out its work: a
later kernel is read against the same counts.

A prefilled token passes through, a layer held: on a `kda` layer the
projections of q, k and v, beta's, the four of rank `head_dim` (the
decay's pair and the gate's pair) and `W_o`, the three convolutions
and, a head, the delta rule's recurrence as written (the decay of S a
key channel, S^T k, the outer product that corrects S, S^T q: 7 x dk x
dv; a chunked form computes more, which does not count); on an `mla`
layer `W_q`, `W_kva`, `W_o` and `W_kvb` a head at a time on both sides
(absorbed), and the two products against the latents of the keys
before it; then the dense SwiGLU, or the router, the shared expert and
this chip's share of the token's `num_experts_per_token` experts; and
the head once.

A decode step reads every matrix held once (a routed expert's only if
a pair fell on it: `touched` a layer), reads and writes every slot's
delta state and convolution rows, and reads the cached latent and
shared key channels of every slot's context in the latent layers.
"""

from __future__ import annotations

from benchmark.flops.decoder import least_seconds  # noqa: F401

_STATE_BYTES = {"float32": 4, "bfloat16": 2}


def layers(config):
    """{"kda", "mla", "dense", "sparse"}: how many of the layers held
    have each mixer and each FFN."""
    held = config["deployment"]["layers_held"]
    full = config["linear_attn_config"]["full_attn_layers"]
    mla = sum(i + 1 in full for i in held)
    dense = sum(i < config["first_k_dense_replace"] for i in held)
    return {"kda": len(held) - mla, "mla": mla, "dense": dense,
            "sparse": len(held) - dense}


def _heads_and_size(config):
    linear = config["linear_attn_config"]
    return linear["num_heads"], linear["head_dim"]


def _state_elements(config):
    """Elements of one layer's delta state a slot: H x dk x dv, both
    the published `head_dim`."""
    heads, size = _heads_and_size(config)
    return heads * size * size


def conv_width(config):
    """Channels the three convolutions run over together: q, k and v."""
    heads, size = _heads_and_size(config)
    return 3 * heads * size


def kda_params(config):
    """Matrices of a KDA layer a token is multiplied with: q, k and v,
    beta, the decay's and the gate's pairs through `head_dim` channels,
    `W_o`."""
    heads, size = _heads_and_size(config)
    d, wide = config["hidden_size"], heads * size
    return d * (conv_width(config) + heads) + 2 * (d * size + size * wide) \
        + wide * d


def mla_params(config):
    """Weights of a latent layer's projections a token is multiplied
    with, `W_kvb` counted a head at a time on both sides."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    c, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    nope, v = config["qk_nope_head_dim"], config["v_head_dim"]
    return d * h * (nope + rope) + d * (c + rope) + c * h * (nope + v) \
        + h * v * d


def dense_params(config):
    return 3 * config["hidden_size"] * config["intermediate_size"]


def expert_params(config):
    """One expert, routed or shared: three matrices."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def router_params(config):
    return config["hidden_size"] * config["deployment"]["router_width"]


def held_share(config):
    """The share of a token's pairs a uniform router deals this chip."""
    share = config["deployment"]
    return share["experts_held"][1] / share["router_width"]


def latent_bytes_per_token(config, itemsize=2):
    """What a token leaves in one latent layer: the latent and the
    shared key channels."""
    return (config["kv_lora_rank"] + config["qk_rope_head_dim"]) * itemsize


def state_bytes_per_slot(config, itemsize=2):
    """Bytes of state a slot holds for one KDA layer: the matrix a head
    in `state_dtype` and the three convolutions' carried rows."""
    rows = config["linear_attn_config"]["short_conv_kernel_size"] - 1
    return _state_elements(config) * _STATE_BYTES[config["state_dtype"]] \
        + rows * conv_width(config) * itemsize


def _recurrence_flops(config):
    """The delta rule for one token of one layer, as the recurrence
    has it."""
    return 7 * _state_elements(config)


def prefill_flops_per_token(config, context):
    """FLOPs of one prompt token with `context` keys before it (itself
    included), over the layers held and the head."""
    n = layers(config)
    h = config["num_attention_heads"]
    taps = config["linear_attn_config"]["short_conv_kernel_size"]
    flops = 2 * config["hidden_size"] * config["vocab_size"]
    flops += n["kda"] * (2 * kda_params(config)
                         + 2 * taps * conv_width(config)
                         + _recurrence_flops(config))
    flops += n["mla"] * (2 * mla_params(config) + 2 * context * h * (
        2 * config["kv_lora_rank"] + config["qk_rope_head_dim"]))
    flops += n["dense"] * 2 * dense_params(config)
    flops += n["sparse"] * 2 * (router_params(config) + expert_params(config)
                                * (config["num_shared_experts"]
                                   + held_share(config)
                                   * config["num_experts_per_token"]))
    return flops


def train_flops_per_token(config, seq):
    """Forward and backward of a token at the mean context of a
    sequence of `seq`, three times the forward pass: the name every
    family's file has; this family is served, and no cell trains it."""
    return 3 * prefill_flops_per_token(config, max(1, seq // 2))


def experts_bytes_per_step(config, touched, itemsize=2):
    """Bytes of expert matrices a decode step reads over the sparse
    layers held: the router, the shared expert and `touched` routed
    experts a layer, at most those held."""
    touched = min(touched, config["deployment"]["experts_held"][1])
    return int(layers(config)["sparse"] * itemsize * (
        router_params(config) + expert_params(config)
        * (config["num_shared_experts"] + touched)))


def latent_bytes_per_step(config, slots, context, itemsize=2):
    """Bytes of cached latents and shared key channels a decode step
    reads over the latent layers held, each slot holding `context`
    keys."""
    return layers(config)["mla"] * slots * context \
        * latent_bytes_per_token(config, itemsize)


def decode_step_bytes(config, slots, context, touched=None, itemsize=2):
    """Bytes a decode step of `slots` slots has to move, each slot
    holding `context` keys: the weights once (`touched` routed experts
    a sparse layer; unless given, as many as a uniform router's pairs
    hit at most), the head among them, every slot's delta state and
    carried rows read and written, the slots' latents read."""
    n = layers(config)
    if touched is None:
        touched = slots * config["num_experts_per_token"] * held_share(config)
    weights = n["kda"] * (kda_params(config) + conv_width(config)
                          * config["linear_attn_config"][
                              "short_conv_kernel_size"]) \
        + n["mla"] * mla_params(config) + n["dense"] * dense_params(config) \
        + config["hidden_size"] * config["vocab_size"]
    return int(weights * itemsize
               + experts_bytes_per_step(config, touched, itemsize)
               + slots * n["kda"] * 2 * state_bytes_per_slot(config, itemsize)
               + latent_bytes_per_step(config, slots, context, itemsize))


def delta_scan_ops_and_bytes(config, tokens, calls, itemsize=2):
    """(FLOPs, bytes) of the delta rule over the KDA layers held for
    prefills of `tokens` real tokens in `calls` calls of one row each:
    the recurrence's operations a token; q, k, v and the gate's z in
    and o out once a token in `itemsize`, the decay a key channel and
    beta in float32, the state read and written once a row and call."""
    heads, size = _heads_and_size(config)
    a_token = (conv_width(config) + 2 * heads * size) * itemsize \
        + (heads * size + heads) * 4
    state = 2 * _state_elements(config) \
        * _STATE_BYTES[config["state_dtype"]]
    n = layers(config)["kda"]
    return (n * tokens * _recurrence_flops(config),
            n * (tokens * a_token + calls * state))


def delta_update_bytes(config, slots):
    """Bytes the recurrence of one decode step moves over the KDA
    layers held: every slot's state read and written."""
    return 2 * slots * layers(config)["kda"] * _state_elements(config) \
        * _STATE_BYTES[config["state_dtype"]]
