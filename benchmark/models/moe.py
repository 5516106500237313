"""Model adapter of the family `moe`: the program's Mixtral-shaped
sparse-expert decoder (`ray_tpu/models/moe.py`), which can be trained
only: it has no cached forward pass, so the serving names are left out
(`models/dense.py` says what an adapter holds)."""

from __future__ import annotations

from benchmark.models.dense import (debug, decoder_fields,  # noqa: F401
                                    with_remat)
from ray_tpu.models.moe import (MoEConfig, init_moe_params_sharded,
                                moe_loss_fn)


def program_config(config):
    return MoEConfig(
        **decoder_fields(config), n_experts=config["num_local_experts"],
        n_experts_per_token=config["num_experts_per_tok"],
        aux_loss_coeff=float(config["router_aux_loss_coef"]))


init_sharded = init_moe_params_sharded
loss = moe_loss_fn
