"""Operations and bytes GLM-5.2's served share needs, from shapes alone
(`decoder.py` says what counts), for one chip's share as the
configuration's `deployment` cuts it: the layers held, attention and
the shared expert whole, of the routed experts the share a uniform
router deals this chip.

A prefilled token passes through, a layer: the latent-attention
projections (`Wqa`, `Wqb`, `Wkva`, `Wo`; `Wkvb` is absorbed, so each
head's query goes through its key half and each head's output through
its value half), on a `full` layer the indexer's three projections and
its scores against every key before it, attention's two products
against the latents of the keys it attends (at most `index_topk`; the
program's masked dense products compute more, which does not count),
the dense FFN or the router, the shared expert and this chip's share of
the token's `num_experts_per_tok` experts; and the head once.

A decode step reads every weight held once (the routed experts held at
most once each: a step's few pairs touch some of them) and, a slot,
the cached latent, rotary key and indexer key of its context.
"""

from __future__ import annotations


def _layers(config):
    return [(config["mlp_layer_types"][i], config["indexer_types"][i])
            for i in config["deployment"]["layers_held"]]


def attention_params(config):
    """Weights of the projections a token is multiplied with, a layer,
    `Wkvb` counted a head at a time on both sides."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    r, c = config["q_lora_rank"], config["kv_lora_rank"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    v = config["v_head_dim"]
    return (d * r + r * h * (nope + rope) + d * (c + rope)
            + c * h * (nope + v) + h * v * d)


def indexer_params(config):
    return (config["q_lora_rank"] * config["index_n_heads"]
            * config["index_head_dim"]
            + config["hidden_size"] * (config["index_head_dim"]
                                       + config["index_n_heads"]))


def expert_params(config):
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def prefill_flops_per_token(config, context):
    """FLOPs of one prompt token with `context` keys before it
    (itself included), over the layers held and the head."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    c, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    attended = min(context, config["index_topk"])
    share = config["deployment"]["experts_held"][1] \
        / config["deployment"]["router_width"]
    flops = 2 * d * config["vocab_size"]
    for ffn, indexer in _layers(config):
        flops += 2 * attention_params(config)
        flops += 2 * attended * h * (2 * c + rope)   # scores and sum
        if indexer == "full":
            flops += 2 * indexer_params(config) + 2 * context \
                * config["index_n_heads"] * config["index_head_dim"]
        if ffn == "dense":
            flops += 2 * 3 * d * config["intermediate_size"]
        else:
            flops += 2 * d * config["deployment"]["router_width"] \
                + 2 * expert_params(config) * (
                    config["n_shared_experts"]
                    + share * config["num_experts_per_tok"])
    return flops


def train_flops_per_token(config, seq):
    """Forward and backward of a token at the mean context of a
    sequence of `seq`, three times the forward pass: the name every
    family's file has; this family is served, and no cell trains it."""
    return 3 * prefill_flops_per_token(config, max(1, seq // 2))


def decode_step_bytes(config, slots, context, itemsize=2):
    """Bytes a decode step of `slots` slots has to read, each slot
    holding `context` keys: the weights once, the slots' caches."""
    weights = 2 * config["hidden_size"] * config["vocab_size"]
    cache = 0
    for ffn, indexer in _layers(config):
        weights += attention_params(config)
        cache += config["kv_lora_rank"] + config["qk_rope_head_dim"]
        if indexer == "full":
            weights += indexer_params(config)
            cache += config["index_head_dim"]
        if ffn == "dense":
            weights += 3 * config["hidden_size"] * config["intermediate_size"]
        else:
            pairs = slots * config["num_experts_per_tok"] \
                * config["deployment"]["experts_held"][1] \
                / config["deployment"]["router_width"]
            weights += config["hidden_size"] \
                * config["deployment"]["router_width"] \
                + expert_params(config) * (
                    config["n_shared_experts"]
                    + min(pairs, config["deployment"]["experts_held"][1]))
    return int((weights + slots * context * cache) * itemsize)
