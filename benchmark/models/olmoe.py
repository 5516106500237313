"""Model adapter of the family `olmoe`: OLMoE-1B-7B through the
program's sparse-expert decoder (`ray_tpu/models/moe.py`), with the
RMSNorm on q and k that every model of the family has and the gates as
its file's `norm_topk_prob` says. Trained only, as the family `moe`:
the decoder has no cached forward pass, so the serving names are left
out (`models/dense.py` says what an adapter holds)."""

from __future__ import annotations

import copy

from benchmark.models.dense import decoder_fields, with_remat  # noqa: F401
from ray_tpu.models.moe import (MoEConfig, init_moe_params_sharded,
                                moe_loss_fn)


def program_config(config):
    return MoEConfig(
        **decoder_fields(config), n_experts=config["num_experts"],
        n_experts_per_token=config["num_experts_per_tok"],
        aux_loss_coeff=float(config["router_aux_loss_coef"]),
        norm_topk_prob=bool(config["norm_topk_prob"]), qk_norm=True)


init_sharded = init_moe_params_sharded
loss = moe_loss_fn


def debug(config):
    """Still the family's shape: full multi-head attention, more
    experts than a token uses by a factor of four."""
    config = copy.deepcopy(config)
    config.update(vocab_size=512, hidden_size=64, intermediate_size=32,
                  num_attention_heads=4, num_key_value_heads=4,
                  max_position_embeddings=256, num_hidden_layers=2,
                  num_experts=8, num_experts_per_tok=2)
    return config
