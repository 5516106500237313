"""Model zoo, TPU-first.

Pure-JAX pytree models (no framework lock-in) annotated with the logical
sharding axes from `ray_tpu.parallel.sharding`, so the same model code runs
single-chip, FSDP, tensor-parallel, and context-parallel by swapping mesh +
rules. The reference has no model zoo of its own (it wraps torch modules);
these exist because the TPU framework's Train/Serve/RL layers need
first-class compiled models to schedule.

- ``decoder`` — the one decoder stack (block, layer scan, parameter
  skeleton, output head, loss tail), handed a sequence mixer and an FFN
- ``llama`` — Llama-3-family decoder LM (GQA, RoPE, SwiGLU), the flagship:
  `decoder` with self-attention or attention through the slot cache
- ``moe``   — sparse-expert decoder (Mixtral, OLMoE): `decoder` with
  `llama`'s self-attention and the dropless expert layer
- ``glm_dsa`` — GLM-5.2's decoder, served: `decoder` over runs of unlike
  layers with latent attention, the sparse-attention indexer, and
  `moe`'s expert layer with a shared expert and a held share
- ``mamba2`` — the Mamba-2 state-space mixer through two cache leaves
  with no sequence axis: the recurrence for a decode step, the chunked
  scan for a prefill
- ``nemotron_h`` — Nemotron 3 Super's hybrid decoder, served: blocks of
  a mixer or an FFN alone, `mamba2`, attention with no positional
  encoding, and `moe`'s expert layer with relu^2 experts in a latent
- ``serving`` — which configs have a cached forward pass, for the engine
- ``mlp``   — small MLP classifier (the fashion-MNIST baseline workload)
- ``training`` — TrainState + sharded train-step factory
- ``hf``, ``memory_plan`` — Hugging Face weight import; HBM planning
"""

from ray_tpu.models.llama import (  # noqa: F401
    LlamaConfig,
    init_params,
    init_params_sharded,
    forward,
    loss_fn,
    param_logical_axes,
)
from ray_tpu.models.mlp import MLPConfig, mlp_init, mlp_forward  # noqa: F401
from ray_tpu.models.training import (  # noqa: F401
    TrainState,
    make_optimizer,
    make_train_step,
    init_train_state,
)
