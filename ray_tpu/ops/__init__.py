"""TPU kernels (Pallas) and reference implementations for the hot ops.

The reference framework delegates all device compute to torch/CUDA; here
the compute path is XLA, and the handful of ops XLA does not fuse optimally
get hand-written Pallas TPU kernels with pure-JAX reference fallbacks (used
on CPU and in interpret-mode tests):

- ``attention``     — flash attention (tiled online-softmax, MXU-shaped)
- ``norms``         — RMSNorm / LayerNorm (plain JAX)
- ``rope``          — rotary position embeddings
- ``cross_entropy`` — blockwise softmax cross-entropy (no full-vocab
                      probability materialization)
- ``grouped_matmul`` — a served share of experts' grouped products, the
                      matrices read where they lie in a run's stack
                      (``lax.ragged_dot`` off the TPU)
- ``stacked_product`` — a decode step's product of a few rows with one
                      layer's matrix, the matrix read where, and in the
                      order, it lies in a run's stack (``jnp.einsum`` on
                      the layer's slice off the TPU)
- ``delta_update``  — the gated delta rule's one-token recurrence, a
                      head's state read once and written back where it
                      lies in a run's stack (the plain recurrence on the
                      sliced layer off the TPU)
- ``ssm_update``    — the Mamba-2 one-token recurrence the same way: a
                      head's state read once and written back where it
                      lies, y's sum over the state's last axis on the
                      matmul unit at float32's rounding (the plain
                      recurrence on the sliced layer off the TPU)
"""

from ray_tpu.ops.attention import flash_attention  # noqa: F401
from ray_tpu.ops.norms import layer_norm, rms_norm_reference  # noqa: F401
from ray_tpu.ops.rope import apply_rope, rope_frequencies  # noqa: F401
from ray_tpu.ops.cross_entropy import softmax_cross_entropy  # noqa: F401
