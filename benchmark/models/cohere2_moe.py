"""Model adapter of the family `cohere2_moe`: Command A+
(command-a-plus-05-2026) through the program's decoder of parallel
blocks with windowed and full layers (`ray_tpu/models/cohere2_moe.py`).
Served only: the program has no loss for it, so the training names are
left out (`models/dense.py` says what an adapter holds).

The file keeps the published `layer_types` whole; the layers this
chip's share holds are the entries `deployment.layers_held` of it. Of
the `deployment.router_width` experts the router chooses among, the
program holds the range `deployment.experts_held` (first, count),
`num_experts` of them.
"""

from __future__ import annotations

import copy
import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import cohere2_moe
from ray_tpu.models.cohere2_moe import Cohere2MoeConfig
from ray_tpu.serve.llm import prefill_bucket

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def program_config(config):
    share = config["deployment"]
    kinds = tuple(config["layer_types"][i].split("_")[0]
                  for i in share["layers_held"])
    first, count = share["experts_held"]
    assert len(kinds) == config["num_hidden_layers"]
    assert count == config["num_experts"]
    assert config["expert_selection_fn"] == "sigmoid"
    assert config["use_parallel_block"] and config["use_gated_activation"]
    assert config["rms_norm_eps"] is None and not config["use_qk_norm"]
    assert config["first_k_dense_replace"] == 0
    assert config["position_embedding_type"] == "rope_gptj" \
        and config["rotary_pct"] == 1
    width = config["intermediate_size"]
    return Cohere2MoeConfig(
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        n_layers=len(kinds), n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_size=config["head_dim"], hidden_dim=width,
        max_seq_len=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["layer_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        dtype=_DTYPES[config["torch_dtype"]],
        n_experts=share["router_width"],
        n_experts_per_token=config["num_experts_per_tok"],
        norm_topk_prob=bool(config["norm_topk_prob"]), scoring="sigmoid",
        shared_hidden_dim=config["num_shared_experts"] * width,
        n_shared_experts=config["num_shared_experts"],
        shared_combination={"average": "average"}[
            config["shared_expert_combination_strategy"]],
        experts_held=(first, count), layer_types=kinds,
        sliding_window=config["sliding_window"],
        logit_scale=float(config["logit_scale"]))


def with_layers(cfg, n):
    """The last n layers of the share: of this family's shares the last
    two are a sliding layer and the full one above it."""
    assert n >= 2, "the check needs a sliding and a full layer"
    return dataclasses.replace(cfg, n_layers=n,
                               layer_types=cfg.layer_types[-n:])


# What the benchmark's weights differ from the program's initialiser
# in: one scale and one vector of signs (the configuration's `assumed`
# has this family's reading of both).
#
# Every routed expert's down-projection is `ROUTED_OUT_SCALE` of the
# initialiser's, for the reason `models/glm_dsa.py` gives at length for
# its own. The runner holds the largest logit error over every position
# under a limit, and a top-k router is not continuous: of 128 experts
# scored by random weights through a sigmoid the 8th and the 9th lie
# closer than bfloat16 activations resolve at about one position in a
# hundred, a float32 reference then chooses another expert there, and
# at the initialiser's own scale that one expert moves the position's
# logits by four times what lower precision moves them by, the
# program's and every fault's alike (PERF.md section 6, PR 39). At this
# scale it moves them by less than bfloat16's own error.
# `tools/glm_logit_check.py --config command-a-plus-serve --weights
# plain` holds the plain weights by a median and a 99th percentile. The
# router has no selection bias, so GLM-5.2's second scale has nothing
# to act on here.
ROUTED_OUT_SCALE = 1 / 32


def final_norm_signs(cfg, key):
    """The final norm's weight, +1 or -1 a channel by the seed, where
    the initialiser has ones. The head is the embedding, and random
    layers leave the stream mostly the input token's own embedding, so
    under a weight of ones a token's largest logit is its own (49
    against 5 for the best other): greedy decoding repeats a prompt's
    last token for the whole answer, a request's routing never
    changes, and the held experts a decode step reads, and so the step,
    are those the seed's few requests happen to hit (15.84 to 16.75 ms
    over four seeds, PERF.md sections 6 and 7, PR 39). Signs keep every
    operation, byte and magnitude of the norm and take the embedding's
    likeness to itself out of the head: the largest logit is then a
    chance one among 32,768 (5.3), generation wanders as a trained
    model's does, and every decode step routes its tokens anew."""
    flip = jax.random.bernoulli(jax.random.fold_in(key, 39), 0.5, (cfg.dim,))
    return jnp.where(flip, -1, 1).astype(cfg.dtype)


def init(cfg, key):
    params = cohere2_moe.init_params(cfg, key)
    return {**params, "final_norm": final_norm_signs(cfg, key),
            "runs": [{**run, "we2": run["we2"] * ROUTED_OUT_SCALE}
                     for run in params["runs"]]}


def init_cache(cfg, rows, max_seq):
    """The model's cache and, for the runner's check, what each row was
    prefilled with (`prompt`, [rows, 0] until a prefill) and the
    position its rings stand after (`ends`)."""
    return {"model": cohere2_moe.init_cache(cfg, rows, max_seq),
            "prompt": jnp.zeros((rows, 0), jnp.int32),
            "ends": jnp.zeros(rows, jnp.int32)}


def cached_forward(params, tokens, cfg, cache, start_pos):
    """Prefill and decode through the cache, the logits of every
    position. A prefill (from position 0) is padded to the engine's
    bucket as the engine pads it, and the rings are left as after the
    last real token: the bucket's padding is in the compared path.

    The runner's check prefills every row with the same number of
    tokens and then has each row decode from its own, shorter length.
    The full layer masks the keys past a row's position; a ring cannot
    be rewound, the keys a shorter row needs at its window's far end
    were overwritten. So a decode step whose rows do not stand where
    their rings do first prefills the rows' prompts again, padded as
    before, leaving each row's rings after its own position: what the
    engine does for a slot whose prompt is that long
    (`models/nemotron_h.py` does the same for a recurrent state)."""
    t = tokens.shape[1]
    if t > 1:
        padded = jnp.pad(tokens, ((0, 0), (0, prefill_bucket(t) - t)))
        logits, model = cohere2_moe.forward_with_cache(
            params, padded, cfg, cache["model"], start_pos, at=t - 1, keep=t)
        return logits, {"model": model, "prompt": padded,
                               "ends": start_pos + t}

    def again(model):
        return cohere2_moe.forward_with_cache(
            params, cache["prompt"], cfg, model, jnp.zeros_like(start_pos),
            at=start_pos - 1)[1]

    model = cache["model"]
    if cache["prompt"].shape[1]:
        model = lax.cond((cache["ends"] != start_pos).any(), again,
                         lambda model: model, model)
    logits, model = cohere2_moe.forward_with_cache(params, tokens, cfg,
                                                   model, start_pos)
    return logits, {**cache, "model": model, "ends": start_pos + 1}


def deployment_args(cfg, params_fn):
    return (cfg, params_fn), {}


def debug(config):
    """Still the family's shape: one period, three sliding layers under
    a full one, a window of 8 keys that the CPU tests' prompts wrap
    several times, four shared experts, a quarter of the router's
    experts held. In float32."""
    config = copy.deepcopy(config)
    config.update(
        vocab_size=512, hidden_size=64, intermediate_size=32,
        num_attention_heads=8, num_key_value_heads=2, head_dim=16,
        sliding_window=8, num_experts=4, num_experts_per_tok=3,
        num_hidden_layers=4, max_position_embeddings=256,
        torch_dtype="float32")
    config["deployment"].update(layers_held=[4, 5, 6, 7], router_width=16,
                                experts_held=[4, 4])
    return config
