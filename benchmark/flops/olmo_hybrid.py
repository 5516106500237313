"""Operations and bytes Olmo Hybrid's served share needs, from shapes
alone (`decoder.py` says what counts), for the layers the
configuration's `deployment` holds on this chip. Nothing here knows the
program's chunk size or how it lays out its work: a later kernel is
read against the same counts.

A prefilled token passes through, a layer held: on a
`linear_attention` layer the six projections in and the one out, the
three convolutions and, a head, the delta rule's recurrence as written
(the decay of S, S^T k, the outer product that corrects S, S^T q: 7 x
dk x dv; a chunked form computes more, which does not count); on a
`full_attention` layer the four projections and the two products
against the keys before it; the SwiGLU on both; and the head once.

A decode step reads every weight held once (of the embedding only a
row a slot, which counts nothing), reads and writes every slot's delta
state and convolution rows, and reads the cached keys and values of
every slot's context.
"""

from __future__ import annotations

from benchmark.flops.decoder import least_seconds  # noqa: F401

_STATE_BYTES = {"float32": 4, "bfloat16": 2}


def _kinds(config):
    return [config["layer_types"][i]
            for i in config["deployment"]["layers_held"]]


def _delta_layers(config):
    return _kinds(config).count("linear_attention")


def _state_elements(config):
    """Elements of one layer's delta state a slot: H x dk x dv."""
    return config["linear_num_value_heads"] * config["linear_key_head_dim"] \
        * config["linear_value_head_dim"]


def conv_width(config):
    """Channels the three convolutions run over together: q, k and v."""
    return config["linear_num_key_heads"] * 2 \
        * config["linear_key_head_dim"] \
        + config["linear_num_value_heads"] * config["linear_value_head_dim"]


def delta_params(config):
    """Matrices of a delta layer a token is multiplied with: q, k, v,
    the gate, a and b in, `wo` out."""
    heads = config["linear_num_value_heads"]
    values = heads * config["linear_value_head_dim"]
    return config["hidden_size"] * (conv_width(config) + values
                                    + 2 * heads) \
        + values * config["hidden_size"]


def attention_params(config):
    d = config["hidden_size"]
    k = d // config["num_attention_heads"]
    return d * k * (2 * config["num_attention_heads"]
                    + 2 * config["num_key_value_heads"])


def swiglu_params(config):
    return 3 * config["hidden_size"] * config["intermediate_size"]


def kv_bytes_per_token(config, itemsize=2):
    """Keys and values a token leaves in one `full_attention` layer."""
    d = config["hidden_size"]
    return 2 * config["num_key_value_heads"] \
        * (d // config["num_attention_heads"]) * itemsize


def state_bytes_per_slot(config, itemsize=2):
    """Bytes of state a slot holds for one delta layer: the matrix a
    head in `state_dtype` and the three convolutions' carried rows."""
    return _state_elements(config) * _STATE_BYTES[config["state_dtype"]] \
        + (config["linear_conv_kernel_dim"] - 1) * conv_width(config) \
        * itemsize


def _recurrence_flops(config):
    """The delta rule for one token of one layer, as the recurrence
    has it."""
    return 7 * _state_elements(config)


def prefill_flops_per_token(config, context):
    """FLOPs of one prompt token with `context` keys before it
    (itself included), over the layers held and the head."""
    d = config["hidden_size"]
    flops = 2 * d * config["vocab_size"]
    for kind in _kinds(config):
        flops += 2 * swiglu_params(config)
        if kind == "linear_attention":
            flops += 2 * delta_params(config) \
                + 2 * config["linear_conv_kernel_dim"] * conv_width(config) \
                + _recurrence_flops(config)
        else:
            flops += 2 * attention_params(config) + 2 * 2 * context * d
    return flops


def train_flops_per_token(config, seq):
    """Forward and backward of a token at the mean context of a
    sequence of `seq`, three times the forward pass: the name every
    family's file has; this family is served, and no cell trains it."""
    return 3 * prefill_flops_per_token(config, max(1, seq // 2))


def decode_step_bytes(config, slots, context, itemsize=2):
    """Bytes a decode step of `slots` slots has to move, each slot
    holding `context` keys: the weights once, the state of every slot
    read and written, the slots' keys and values read."""
    weights = config["hidden_size"] * config["vocab_size"]
    state = cache = 0
    for kind in _kinds(config):
        weights += swiglu_params(config)
        if kind == "linear_attention":
            weights += delta_params(config)
            state += 2 * state_bytes_per_slot(config, itemsize)
        else:
            weights += attention_params(config)
            cache += kv_bytes_per_token(config, itemsize)
    return int(weights * itemsize + slots * state + slots * context * cache)


def delta_scan_ops_and_bytes(config, tokens, calls, itemsize=2):
    """(FLOPs, bytes) of the delta rule over the delta layers held for
    prefills of `tokens` real tokens in `calls` calls of one row each:
    the recurrence's operations a token; q, k, v, a and b in and o out
    once a token, the state read and written once a row and call."""
    heads = config["linear_num_value_heads"]
    values = heads * config["linear_value_head_dim"]
    a_token = (conv_width(config) + values) * itemsize + 2 * heads * 4
    state = 2 * _state_elements(config) \
        * _STATE_BYTES[config["state_dtype"]]
    layers = _delta_layers(config)
    return (layers * tokens * _recurrence_flops(config),
            layers * (tokens * a_token + calls * state))


def delta_update_bytes(config, slots):
    """Bytes the recurrence of one decode step moves over the delta
    layers held: every slot's state read and written."""
    return 2 * slots * _delta_layers(config) * _state_elements(config) \
        * _STATE_BYTES[config["state_dtype"]]
