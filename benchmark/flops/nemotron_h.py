"""Operations and bytes Nemotron 3 Super's served share needs, from
shapes alone (`decoder.py` says what counts), for one chip's share as
the configuration's `deployment` cuts it: the layers held, the mixers
and the shared expert whole, of the routed experts the share a uniform
router deals this chip.

A prefilled token passes through, by the letter of each layer held: `M`
the Mamba-2 projections in and out, the convolution and, a head, the
state's update and read (2 x 2 x P x N); `*` the four attention
projections and the two products against the keys before it; `E` the
router, the latent pair, the shared expert and this chip's share of the
token's `num_experts_per_tok` experts; and the head once.

A decode step reads every weight held once (a routed expert's only if
a pair fell on it: `touched` a layer, all of them unless given), reads
and writes every slot's recurrent state and convolution rows, and reads
the cached keys and values of every slot's context.
"""

from __future__ import annotations


def _letters(config):
    return [config["hybrid_override_pattern"][i]
            for i in config["deployment"]["layers_held"]]


def conv_width(config):
    return config["mamba_num_heads"] * config["mamba_head_dim"] \
        + 2 * config["n_groups"] * config["ssm_state_size"]


def mamba_params(config):
    """Matrices of a Mamba-2 layer a token is multiplied with."""
    inner = config["mamba_num_heads"] * config["mamba_head_dim"]
    return config["hidden_size"] * (
        inner + conv_width(config) + config["mamba_num_heads"]) \
        + inner * config["hidden_size"]


def attention_params(config):
    d, k = config["hidden_size"], config["head_dim"]
    return d * k * (2 * config["num_attention_heads"]
                    + 2 * config["num_key_value_heads"])


def expert_params(config):
    """One routed expert: two matrices in the latent."""
    return 2 * config["moe_latent_size"] * config["moe_intermediate_size"]


def expert_layer_params(config):
    """An expert layer outside its routed experts: the router, the
    latent pair and the shared expert."""
    d = config["hidden_size"]
    return d * config["deployment"]["router_width"] \
        + 2 * d * config["moe_latent_size"] \
        + 2 * d * config["n_shared_experts"] \
        * config["moe_shared_expert_intermediate_size"]


def state_bytes_per_slot(config, layer="M"):
    """Bytes of state a slot holds for one Mamba-2 layer: the recurrent
    state in `ssm_state_dtype` and the convolution's rows."""
    if layer != "M":
        return 0
    state = {"float32": 4, "bfloat16": 2}[config["ssm_state_dtype"]]
    return config["mamba_num_heads"] * config["mamba_head_dim"] \
        * config["ssm_state_size"] * state \
        + (config["conv_kernel"] - 1) * conv_width(config) * 2


def prefill_flops_per_token(config, context):
    """FLOPs of one prompt token with `context` keys before it
    (itself included), over the layers held and the head."""
    share = config["deployment"]["experts_held"][1] \
        / config["deployment"]["router_width"]
    flops = 2 * config["hidden_size"] * config["vocab_size"]
    for letter in _letters(config):
        if letter == "M":
            flops += 2 * mamba_params(config) \
                + 2 * config["conv_kernel"] * conv_width(config) \
                + 2 * 2 * config["mamba_num_heads"] \
                * config["mamba_head_dim"] * config["ssm_state_size"]
        elif letter == "*":
            flops += 2 * attention_params(config) + 2 * 2 * context \
                * config["num_attention_heads"] * config["head_dim"]
        else:
            flops += 2 * expert_layer_params(config) + 2 * share \
                * config["num_experts_per_tok"] * expert_params(config)
    return flops


def train_flops_per_token(config, seq):
    """Forward and backward of a token at the mean context of a
    sequence of `seq`, three times the forward pass: the name every
    family's file has; this family is served, and no cell trains it."""
    return 3 * prefill_flops_per_token(config, max(1, seq // 2))


def decode_step_bytes(config, slots, context, touched=None, itemsize=2):
    """Bytes a decode step of `slots` slots has to move, each slot
    holding `context` keys: the weights once (`touched` routed experts
    a layer, every held one unless given), the state of every slot read
    and written, the slots' keys and values read."""
    held = config["deployment"]["experts_held"][1]
    touched = held if touched is None else min(touched, held)
    weights = 2 * config["hidden_size"] * config["vocab_size"]
    state = cache = 0
    for letter in _letters(config):
        if letter == "M":
            weights += mamba_params(config)
            state += 2 * state_bytes_per_slot(config)
        elif letter == "*":
            weights += attention_params(config)
            cache += 2 * config["num_key_value_heads"] * config["head_dim"]
        else:
            weights += expert_layer_params(config) \
                + touched * expert_params(config)
    return int(weights * itemsize + slots * state
               + slots * context * cache * itemsize)


def ssm_update_bytes(config, slots):
    """Bytes the recurrence of a decode step moves over the Mamba-2
    layers held: every slot's recurrent state read and written."""
    state = {"float32": 4, "bfloat16": 2}[config["ssm_state_dtype"]]
    return 2 * slots * _letters(config).count("M") * state \
        * config["mamba_num_heads"] * config["mamba_head_dim"] \
        * config["ssm_state_size"]
