"""Operations and bytes the algorithm needs, from shapes alone.

Training FLOPs per token are what the forward and backward passes
require (three times the forward pass): two per multiply-add of every
matrix a token passes through (for sparse experts, only the experts per
token it is routed to, plus the router) and the causal half of
attention's two products. Recomputed operations do not count. The
embedding lookup is a gather and counts nothing.
"""

from __future__ import annotations


def matmul_params_per_token(config):
    """Weights of the matrices one token is multiplied with."""
    d = config["hidden_size"]
    h = config["num_attention_heads"]
    hkv = config["num_key_value_heads"]
    hd = d // h
    attn = d * h * hd + 2 * d * hkv * hd + h * hd * d
    ffn = 3 * d * config["intermediate_size"]
    if "num_local_experts" in config:
        ffn = ffn * config["num_experts_per_tok"] \
            + d * config["num_local_experts"]
    return config["num_hidden_layers"] * (attn + ffn) \
        + d * config["vocab_size"]


def attention_flops_per_token(config, seq):
    """Forward, causal: QK^T and PV each take 2 * (seq / 2) * d per token
    and layer on average."""
    return config["num_hidden_layers"] * 2 * seq * config["hidden_size"]


def train_flops_per_token(config, seq):
    return 3 * (2 * matmul_params_per_token(config)
                + attention_flops_per_token(config, seq))


# Matrix products of one flash kernel, each 2 * b * h * s^2 * hd FLOPs
# when not causal: forward S=QK^T, O=PV; dq: S, dP=dO V^T, dQ=dS K;
# dk/dv: S, dV=P^T dO, dP, dK=dS^T Q.
_FLASH_PRODUCTS = {"flash_fwd": 2, "flash_bwd_dq": 3, "flash_bwd_dkv": 4}
# Tensors of q's shape and of k's shape that a kernel must read or
# write once (bf16), and float32 rows of length s per head (logsumexp,
# delta).
_FLASH_TENSORS = {"flash_fwd": (2, 2, 1),       # q, o | k, v | lse
                  "flash_bwd_dq": (3, 2, 2),    # q, do, dq | k, v | lse, delta
                  "flash_bwd_dkv": (2, 4, 2)}   # q, do | k, v, dk, dv | ...


def flash_ops_and_bytes(kernel, *, batch, seq, n_heads, n_kv_heads,
                        head_dim, causal=True, itemsize=2):
    """(FLOPs, bytes) of one call of a flash-attention kernel."""
    ops = _FLASH_PRODUCTS[kernel] * 2 * batch * n_heads * seq * seq * head_dim
    if causal:
        ops //= 2
    q_like, k_like, rows = _FLASH_TENSORS[kernel]
    nbytes = (q_like * batch * seq * n_heads * head_dim * itemsize
              + k_like * batch * seq * n_kv_heads * head_dim * itemsize
              + rows * batch * n_heads * seq * 4)
    return ops, nbytes


def least_seconds(ops, nbytes, peaks):
    """The roofline: (least time the chip could take, which peak bounds
    it)."""
    t_ops = ops / peaks["bf16_flops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
