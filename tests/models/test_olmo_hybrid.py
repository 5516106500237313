"""What is Olmo Hybrid's alone, at debug widths on the CPU, in float32:
the file building the published model, and the block's norm standing
behind each half. What every served family's tests hold is in
`test_served_contract.py`, over this family's row in `families.py`."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import decoder, llama, olmo_hybrid
from tests.models import families

NAME = "OlmoHybridConfig"
FILE, ADAPTER = families.file(NAME), families.adapter(NAME)
CFG = families.cfg(NAME)


def test_the_file_builds_the_published_model():
    cfg = ADAPTER.program_config(FILE)
    assert cfg.layer_types == olmo_hybrid.PUBLISHED_LAYER_TYPES[:12]
    assert cfg.runs() == [("linear", 3), ("full", 1)] * 3
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.hidden_dim, cfg.vocab_size) == (3840, 30, 30, 128, 11008,
                                                100352)
    assert (cfg.delta_heads, cfg.delta_key_dim, cfg.delta_value_dim,
            cfg.conv_kernel, cfg.allow_neg_eigval) == (30, 96, 192, 4, True)
    assert (cfg.norm_placement, cfg.qk_norm, cfg.tie_embeddings,
            cfg.norm_eps) == ("output", True, False, 1e-6)
    assert cfg.dtype == jnp.bfloat16 and cfg.state_dtype == jnp.float32
    # The defaults are the published model's.
    assert dataclasses.replace(
        cfg, n_layers=32, layer_types=olmo_hybrid.PUBLISHED_LAYER_TYPES) \
        == olmo_hybrid.OlmoHybridConfig()
    assert ADAPTER.with_layers(cfg, 4).runs() == [("linear", 3),
                                                  ("full", 1)]
    debug = olmo_hybrid.OlmoHybridConfig.debug_olmo_hybrid()
    assert debug.delta_key_dim != debug.delta_value_dim
    assert debug.delta_heads & (debug.delta_heads - 1)  # no power of two
    assert CFG == debug


def test_the_benchmarks_weights_are_the_programs_initialisers():
    assert ADAPTER.init is olmo_hybrid.init_params


def test_the_norm_stands_behind_each_half():
    """`decoder.block` with `norm_placement` "output" is x + norm(half(x))
    for both halves, written out; "input" is another function."""
    params = families.params(NAME)
    cfg = dataclasses.replace(CFG, n_layers=1, layer_types=("full",))
    lp = jax.tree.map(lambda x: x[0], params["runs"][1])
    lp = {**lp, "attn_norm": lp["attn_norm"] * 1.5,
          "mlp_norm": lp["mlp_norm"] * 0.5}
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, cfg.dim))

    def mixer(h, lp, rope, state, handed):
        return jnp.einsum("bsd,dhk->bshk", h, lp["wv"]), state, handed

    ffn = llama.swiglu()
    got, *_ = decoder.block(mixer, ffn, cfg, None, x, lp)
    mixed = jnp.einsum("bshk,hkd->bsd", mixer(x, lp, None, None, None)[0],
                       lp["wo"])
    mid = x + decoder.norm(cfg, mixed, lp["attn_norm"])
    want = mid + decoder.norm(cfg, ffn(mid, lp)[0], lp["mlp_norm"])
    np.testing.assert_allclose(got, want, atol=1e-6)
    other, *_ = decoder.block(
        mixer, ffn, dataclasses.replace(cfg, norm_placement="input"), None,
        x, lp)
    assert float(jnp.abs(other - want).max()) > 0.1
    with pytest.raises(AssertionError):
        decoder.block(mixer, ffn, dataclasses.replace(
            cfg, norm_placement="both"), None, x, lp)
