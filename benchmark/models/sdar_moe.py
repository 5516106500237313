"""Model adapter of the family `sdar_moe`: SDAR-30B-A3B-Chat through the
program's block-diffusion decoder (`ray_tpu/models/sdar_moe.py`:
grouped-query attention with a norm a head on q and k, causal between
blocks of four positions and both ways inside one, a softmax-routed
expert layer in every layer). Served only: the program has no loss for
it, so the training names are left out (`models/dense.py` says what an
adapter holds).
"""

from __future__ import annotations

import copy
import dataclasses

import jax.numpy as jnp

from ray_tpu.models import sdar_moe
from ray_tpu.models.sdar_moe import SdarMoeConfig
from ray_tpu.serve.llm import prefill_bucket

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def program_config(config):
    generation = config["generation"]
    assert config["model_type"] == "sdar_moe"
    assert config["hidden_act"] == "silu" and not config["attention_bias"]
    assert config["decoder_sparse_step"] == 1 and not config["mlp_only_layers"]
    assert config["rope_scaling"] is None and not config["use_sliding_window"]
    assert not config["tie_word_embeddings"]
    return SdarMoeConfig(
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_size=config["head_dim"],
        hidden_dim=config["moe_intermediate_size"],
        max_seq_len=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        dtype=_DTYPES[config["torch_dtype"]],
        n_experts=config["num_experts"],
        n_experts_per_token=config["num_experts_per_tok"],
        norm_topk_prob=bool(config["norm_topk_prob"]), scoring="softmax",
        block_length=generation["block_length"],
        denoising_steps=generation["denoising_steps"],
        mask_token_id=generation["mask_token_id"])


def with_layers(cfg, n):
    return dataclasses.replace(cfg, n_layers=n)


# What the benchmark's weights differ from the program's initialiser
# in: two scales (the configuration's `assumed` has the reading of
# both).
#
# The norms on q and k have a weight of `QK_GAIN` a channel where the
# initialiser has ones. Under ones a query's scores against random keys
# spread by 1, a softmax over several hundred of them is all but
# uniform, attention adds to the stream a twelfth of what the token's
# own embedding is, and an open position (whose input is the mask token
# in every slot, block and step) then has nearly the same hidden state
# everywhere: the 128 open positions of a denoising pass crowd onto the
# same experts, and a forward read 61.6 % of the experts on the chip
# where a trained model, whose hidden state at a masked position is what
# the context makes it, reads them all (PERF.md section 6, PR 50). A
# trained model's attention is peaked, and its q and k norms' weights
# are not ones. At this gain the scores spread by 2.6, a row attends a
# handful of keys, attention adds about what the embedding is, open
# positions differ by slot and by position, and a forward reads 96 % of
# the experts. Every operation and byte is as under ones.
QK_GAIN = 1.6
# Every routed expert's down-projection is `ROUTED_OUT_SCALE` of the
# initialiser's, for the reason `models/glm_dsa.py` gives at length for
# its own: the runner holds the largest logit error over every position
# under a limit, a top-8 router over 128 experts is not continuous, and
# at the initialiser's scale the one expert a float32 reference chooses
# differently at one position in a hundred moves that position's logits
# by more than lower precision moves them. What these weights hide (the
# routed experts' own faults) is held in float32 on the CPU at the plain
# weights (`tests/models/test_sdar_moe.py`).
ROUTED_OUT_SCALE = 1 / 32


def init(cfg, key):
    params = sdar_moe.init_params(cfg, key)
    return {**params, "runs": [
        {**run, "q_norm": run["q_norm"] * QK_GAIN,
         "k_norm": run["k_norm"] * QK_GAIN,
         "we2": run["we2"] * ROUTED_OUT_SCALE} for run in params["runs"]]}


init_cache = sdar_moe.init_cache


def cached_forward(params, tokens, cfg, cache, start_pos):
    """Prefill and block steps through the cache, the logits of every
    position. A call of more than a block is a prefill and is padded to
    the engine's bucket as the engine pads it (the padding lies in
    later blocks, which no real row sees); a call of one block is a
    block step, denoising or commit by what `tokens` holds."""
    t = tokens.shape[1]
    if t > cfg.block_length:
        tokens = jnp.pad(tokens, ((0, 0), (0, prefill_bucket(t) - t)))
    return sdar_moe.forward_with_cache(params, tokens, cfg, cache, start_pos,
                                       keep=t)


def deployment_args(cfg, params_fn):
    return (cfg, params_fn), {}


def debug(config):
    """Still the family's shape: several query heads a key head, more
    experts than a token takes, blocks of four fixed two a step. In
    float32."""
    config = copy.deepcopy(config)
    config.update(
        vocab_size=512, hidden_size=64, moe_intermediate_size=32,
        num_attention_heads=8, num_key_value_heads=2, head_dim=16,
        num_experts=8, num_experts_per_tok=3, num_hidden_layers=2,
        max_position_embeddings=256, torch_dtype="float32")
    config["generation"] = {**config["generation"], "mask_token_id": 511}
    return config
