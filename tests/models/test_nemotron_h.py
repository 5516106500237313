"""What is Nemotron 3 Super's alone, at debug widths on the CPU, in
float32, seeded random weights: the file building the published model,
a published layer being a mixer or an FFN alone, the operations and
bytes counted from the file's shapes, the tool's limits on the plain
weights' statistics, and rows of different lengths decoding together as
each decodes alone. What every served family's tests hold is in
`test_served_contract.py`, over this family's row in `families.py`."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama, nemotron_h
from ray_tpu.models.serving import served_model
from tests.models import families
from tools import glm_logit_check

NAME = "NemotronHConfig"
FILE, ADAPTER = families.file(NAME), families.adapter(NAME)
CFG = families.cfg(NAME)
forward_with_cache = families.forward_with_cache(NAME)


def _tokens(shape, seed=1):
    return families.tokens(NAME, shape, seed)


def test_the_file_builds_the_published_model():
    cfg = ADAPTER.program_config(FILE)
    assert cfg.pattern == "MEMEMEMEM*E" == nemotron_h.PUBLISHED_PATTERN[27:38]
    assert FILE["hybrid_override_pattern"] == nemotron_h.PUBLISHED_PATTERN
    assert cfg.runs() == [(("ssm", "moe"), 4), (("ssm", None), 1),
                          (("attn", "moe"), 1)]
    assert (cfg.dim, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
            cfg.ssm_state, cfg.conv_kernel, cfg.chunk_size) == (
                4096, 128, 64, 8, 128, 4, 128)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (32, 2, 128)
    assert (cfg.n_experts, cfg.n_experts_per_token, cfg.latent_dim,
            cfg.hidden_dim, cfg.shared_hidden_dim, cfg.experts_held,
            cfg.expert_kind, cfg.gate_scale) == (
                512, 22, 1024, 2688, 5376, (0, 128), "relu2", 5.0)
    # The defaults are the published model's.
    assert dataclasses.replace(
        cfg, vocab_size=131072, n_layers=88, experts_held=None,
        pattern=nemotron_h.PUBLISHED_PATTERN) == nemotron_h.NemotronHConfig()
    held = jax.eval_shape(
        lambda: nemotron_h.init_params(cfg, jax.random.PRNGKey(0)))
    assert sum(x.size for x in jax.tree.leaves(held)) == 4_648_163_712
    whole = jax.eval_shape(lambda: nemotron_h.init_params(
        nemotron_h.NemotronHConfig(), jax.random.PRNGKey(0)))
    assert round(sum(x.size for x in jax.tree.leaves(whole)) / 1e8) == 1207
    cache = jax.eval_shape(lambda: nemotron_h.init_cache(cfg, 64, 4096))
    leaves = dict(zip(("state", "rows"), (
        [x for x, s in zip(jax.tree.leaves(cache), jax.tree.leaves(
            nemotron_h.state_leaves(cache))) if s is want]
        for want in (True, False))))
    assert sorted(x.shape[2:] for x in leaves["state"]) == sorted(
        2 * [(128, 64, 128), (3, 10240)])
    assert [x.shape for x in leaves["rows"]] == 2 * [(1, 64, 4096, 2, 128)]
    # 21.3 MB of state a slot, 1 KB of keys and values a token.
    assert sum(x.size * x.dtype.itemsize for x in leaves["state"]) // 64 \
        == 5 * (128 * 64 * 128 * 4 + 3 * 10240 * 2)
    assert sum(x.size * x.dtype.itemsize for x in leaves["rows"]) \
        // (64 * 4096) == 1024
    assert ADAPTER.with_layers(cfg, 5).runs() == [
        (("ssm", "moe"), 1), (("ssm", None), 1), (("attn", "moe"), 1)]


@pytest.mark.parametrize("pattern, blocks", [
    ("MEM*E", (("ssm", "moe"), ("ssm", None), ("attn", "moe"))),
    ("EM", ((None, "moe"), ("ssm", None))),
    ("M*", (("ssm", None), ("attn", None))),
    ("EE*EM", ((None, "moe"), (None, "moe"), ("attn", "moe"),
               ("ssm", None))),
])
def test_a_published_layer_is_a_mixer_or_an_ffn_alone(pattern, blocks):
    """Blocks pair a mixer with the expert layer that follows it; every
    other layer is a block of one half. Each such stack runs."""
    cfg = dataclasses.replace(CFG, pattern=pattern, n_layers=len(pattern))
    assert cfg.blocks == blocks
    params = jax.jit(nemotron_h.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(0))
    logits, cache = forward_with_cache(
        params, _tokens((2, 9)), cfg, nemotron_h.init_cache(cfg, 2, 16),
        jnp.zeros(2, jnp.int32))
    assert logits.shape == (2, 9, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())
    assert len(cache["runs"]) == len(cfg.runs())


def test_operations_and_bytes_are_counted_from_the_files_shapes():
    from benchmark.flops import nemotron_h as flops

    assert flops.mamba_params(FILE) == 4096 * 18560 + 8192 * 4096
    assert flops.attention_params(FILE) == 35_651_584
    assert flops.expert_params(FILE) == 5_505_024
    assert flops.expert_layer_params(FILE) == 4096 * (512 + 2 * 1024
                                                      + 2 * 5376)
    # A decode step reads every matrix held once (all parameters but
    # the norms, the convolutions, A, D and the biases) and reads and
    # writes every slot's state.
    vectors = 5 * (4096 + 8192 + 5 * 10240 + 3 * 128) + 4096 \
        + 5 * (4096 + 512) + 4096
    matrices = 4_648_163_712 - vectors
    assert flops.decode_step_bytes(FILE, 64, 0) == 2 * matrices + 64 * 2 \
        * 5 * (128 * 64 * 128 * 4 + 3 * 10240 * 2)
    # 120 of the 128 held experts touched leaves eight a layer unread.
    assert flops.decode_step_bytes(FILE, 64, 0) \
        - flops.decode_step_bytes(FILE, 64, 0, touched=120) \
        == 2 * 5 * 8 * 5_505_024
    assert flops.decode_step_bytes(FILE, 64, 1000) \
        - flops.decode_step_bytes(FILE, 64, 0) == 64 * 1000 * 1024
    assert flops.ssm_update_bytes(FILE, 64) == 2 * 64 * 5 * 4 * 128 * 64 * 128
    # One attention layer's two products a key.
    near, far = (flops.prefill_flops_per_token(FILE, n) for n in (500, 1500))
    assert far - near == 2 * 2 * 1000 * 32 * 128
    assert flops.train_flops_per_token(FILE, 4096) \
        == 3 * flops.prefill_flops_per_token(FILE, 2048)


def test_the_tools_limits_hold_the_program_and_not_a_fault():
    """Each limit of the file names a statistic the tool gives; the
    plain weights' keep the program and fail a fault."""
    checks = FILE["serve"]["tool_checks"]
    program = families.errors(NAME)
    assert set(glm_logit_check.FAMILIES) >= {"glm_dsa", "nemotron_h",
                                             "cohere2_moe", "olmo_hybrid"}
    assert all(name in program for limits in checks.values()
               for name in limits)
    assert glm_logit_check.within(program, checks["plain"])
    assert not glm_logit_check.within(families.errors(NAME, "no D term"),
                                      checks["plain"])


def test_every_other_served_models_leaves_are_rows():
    dense = llama.LlamaConfig.debug()
    assert not any(jax.tree.leaves(served_model(dense).state_leaves(
        served_model(dense).init_cache(dense, 2, 16))))


def test_rows_of_different_lengths_in_one_decode_batch_equal_each_alone():
    """Each row is prefilled alone into its slot, as the engine admits
    it; the two then decode together from their own positions."""
    params = families.params(NAME)
    lens, steps = (17, 9), 4
    tokens = _tokens((2, max(lens) + steps), seed=4)
    cache = nemotron_h.init_cache(CFG, 2, 32)
    alone = []
    for row, n in enumerate(lens):
        slot = jax.tree.map(lambda x: x[:, row:row + 1], cache)
        _, slot = forward_with_cache(
            params, tokens[row:row + 1, :n], CFG, slot,
            jnp.zeros(1, jnp.int32))
        cache = jax.tree.map(
            lambda x, new: x.at[:, row:row + 1].set(new), cache, slot)
        logits = []
        for i in range(steps):
            out, slot = forward_with_cache(
                params, tokens[row:row + 1, n + i:n + i + 1], CFG, slot,
                jnp.full(1, n + i, jnp.int32))
            logits.append(out[0, 0])
        alone.append(logits)
    at = np.arange(2)
    for i in range(steps):
        pos = np.asarray(lens) + i
        out, cache = forward_with_cache(
            params, tokens[at, pos][:, None], CFG, cache,
            jnp.asarray(pos, jnp.int32))
        for row in range(2):
            np.testing.assert_allclose(out[row, 0], alone[row][i], atol=1e-6)
