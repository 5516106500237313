"""What is GLM-5.2's alone, at debug widths on the CPU, in float32,
seeded random weights (contexts of 40 to 76 against an `index_topk` of
8): the file building the published model, the operations and bytes
counted from its shapes, the tool's statistics and limits, IndexShare,
the selection, the bounded buffer of a held share running over, the one
contract every served model keeps, and the engine serving it through
admission, prefix read-back and copy-in. What every served family's
tests hold is in `test_served_contract.py`, over this family's row in
`families.py`."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu._private.config import ray_config
from ray_tpu.models import glm_dsa, moe
from ray_tpu.models.serving import served_model
from ray_tpu.serve.llm import LLMEngine, SamplingParams
from tests.models import families
from tools import glm_logit_check

NAME = "GlmDsaConfig"
FILE, ADAPTER = families.file(NAME), families.adapter(NAME)
CFG = families.cfg(NAME)


def test_the_file_builds_the_published_model():
    cfg = ADAPTER.program_config(FILE)
    assert cfg.kinds == (("dense", "full"), ("sparse", "shared"),
                         ("sparse", "shared"), ("sparse", "shared"),
                         ("sparse", "full"))
    assert cfg.kinds == glm_dsa.published_kinds(78)[2:7]
    assert glm_dsa.published_kinds(78) == tuple(zip(
        FILE["mlp_layer_types"], FILE["indexer_types"]))
    assert (cfg.dim, cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim) == (
                6144, 64, 2048, 512, 192, 64, 256)
    assert (cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk) == (
        32, 128, 2048)
    assert (cfg.n_experts, cfg.n_experts_per_token, cfg.hidden_dim,
            cfg.dense_hidden_dim, cfg.experts_held) == (
                256, 8, 2048, 12288, (0, 16))
    # The defaults are the published model's.
    assert dataclasses.replace(
        cfg, vocab_size=154880, n_layers=78, layer_kinds=(),
        experts_held=None) == glm_dsa.GlmDsaConfig()
    params = jax.eval_shape(
        lambda: glm_dsa.init_params(cfg, jax.random.PRNGKey(0)))
    assert sum(x.size for x in jax.tree.leaves(params)) == 3_881_517_056
    cache = jax.eval_shape(lambda: glm_dsa.init_cache(cfg, 16, 16384))
    per_token = sum(x.size * x.dtype.itemsize
                    for x in jax.tree.leaves(cache)) // (16 * 16384)
    assert per_token == (5 * 576 + 2 * 128) * 2
    assert all(x.shape[1:3] == (16, 16384) for x in jax.tree.leaves(cache))
    small = ADAPTER.with_layers(cfg, 3)
    assert small.kinds == (("dense", "full"), ("sparse", "shared"),
                           ("sparse", "full"))


def test_operations_and_bytes_are_counted_from_the_files_shapes():
    from benchmark.flops import glm_dsa as flops

    assert flops.attention_params(FILE) == 165_019_648
    assert flops.indexer_params(FILE) == 9_371_648
    assert flops.expert_params(FILE) == 37_748_736
    # A decode step reads every weight held once and the slots' caches.
    # (the matrices: all parameters but the norms and the router's bias;
    # 16 slots x 8 pairs deal this share's 16 experts 8 pairs a layer,
    # so at most 8 of them are read)
    matrices = 3_881_517_056 - 5 * (2 * 6144 + 2048 + 512) \
        - 2 * 2 * 128 - 4 * 256 - 6144
    weights = flops.decode_step_bytes(FILE, 16, 0)
    assert weights == 2 * (matrices - 4 * 8 * 37_748_736)
    assert flops.decode_step_bytes(FILE, 16, 7000) - weights \
        == 16 * 7000 * 6272
    # A token late in a long prompt attends 2048 keys, not its context.
    near, far = (flops.prefill_flops_per_token(FILE, n)
                 for n in (2048, 14000))
    assert far - near == 2 * 2 * (14000 - 2048) * 32 * 128
    assert flops.train_flops_per_token(FILE, 4096) \
        == 3 * flops.prefill_flops_per_token(FILE, 2048)


def test_a_positions_errors_are_told_by_quantiles_too():
    """(Each fault's case in `test_served_contract.py` holds the order
    of its own statistics.)"""
    program = families.errors(NAME)
    assert 0 <= program["q50"] <= program["q99"] <= program["q99.9"] \
        <= program["max"]
    assert program["over"] == 0 \
        < families.errors(NAME, "no shared expert")["over"]
    # What one set of weights cannot show, the other does.
    unseen = glm_logit_check.UNSEEN
    assert not set(unseen["benchmark"]) & set(unseen["plain"])
    assert set(unseen["benchmark"]) | set(unseen["plain"]) \
        < set(families.faults(NAME))
    checks = FILE["serve"]["tool_checks"]
    assert set(checks) == set(unseen)
    assert all(name in program for limits in checks.values()
               for name in limits)
    assert glm_logit_check.within(program, checks["plain"])


def _sparse_layer(seed=3):
    cfg = dataclasses.replace(CFG, experts_held=None)
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    lp = moe.expert_init(cfg, keys[:4])
    y = jax.random.normal(keys[4], (2, 24, cfg.dim))
    return cfg, lp, y


def test_a_crowded_share_takes_more_buffers_and_drops_no_pair(monkeypatch):
    cfg, lp, y = _sparse_layer(seed=4)
    share = dataclasses.replace(cfg, experts_held=(4, 4))
    part = {**lp, **{k: lp[k][4:8] for k in ("we1", "we3", "we2")}}
    roomy, _, _, one = moe._moe_ffn(share, part, y, None, None)
    assert int(one["pair_overflows"]) == 0 < int(one["pairs_held"])
    monkeypatch.setattr(moe, "_HELD_ROWS_MIN", 4)
    monkeypatch.setattr(moe, "_HELD_ROWS_SLACK", 0)
    crowded, _, _, many = moe._moe_ffn(share, part, y, None, None)
    assert int(many["pairs_held"]) == int(one["pairs_held"])
    assert int(many["pair_overflows"]) == -(-int(one["pairs_held"]) // 4) - 1
    np.testing.assert_allclose(crowded, roomy, atol=2e-6)


def test_a_shared_layer_attends_what_its_full_layer_chose(monkeypatch):
    masks, attend = [], glm_dsa._attend

    def recorded(q_lat, q_rope, latent, rope_keys, mask, positions, scale):
        masks.append((np.asarray(mask), np.asarray(positions)))
        return attend(q_lat, q_rope, latent, rope_keys, mask, positions,
                      scale)

    monkeypatch.setattr(glm_dsa, "_attend", recorded)
    params = glm_dsa.init_params(CFG, jax.random.PRNGKey(1))
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, CFG.vocab_size, (2, 32), dtype=np.int32))
    with jax.disable_jit():
        glm_dsa.forward_with_cache(
            params, tokens, CFG, glm_dsa.init_cache(CFG, 2, 64),
            jnp.zeros(2, jnp.int32))
    (full, pos), (shared, _), (above, _) = masks  # one block of queries
    assert np.array_equal(shared, full)
    assert not np.array_equal(above, full)
    for mask in (full, above):
        assert np.array_equal(mask.sum(-1),
                              np.minimum(pos + 1, CFG.index_topk))
        assert not (mask & (np.arange(64) > pos[..., None])).any()


def test_selection_is_lax_top_k_ties_and_short_rows_too():
    rng = np.random.default_rng(0)
    scores = rng.normal(size=(3, 5, 40)).astype(np.float32)
    scores[0, 0, 10:30] = 0.0                        # ties at the k-th
    scores[1, 2] = np.where(np.arange(40) < 6, scores[1, 2], -np.inf)
    scores[2, 4, ::2] = -0.0
    got = np.asarray(glm_dsa._top_k_mask(jnp.asarray(scores), 8))
    want = np.zeros_like(got)
    np.put_along_axis(want, np.asarray(
        jax.lax.top_k(jnp.asarray(scores), 8)[1]), True, -1)
    assert np.array_equal(got, want)
    assert np.asarray(glm_dsa._top_k_mask(jnp.asarray(scores), 64)).all()


def test_every_served_model_keeps_the_one_contract():
    """`forward(..., at)` gives the logits of position `at`, the cache
    and a dict of counts, for the dense decoder as for this one."""
    from ray_tpu.models import llama

    dense = llama.LlamaConfig.debug()
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, 256, (2, 6)), jnp.int32)
    start = jnp.zeros(2, jnp.int32)
    for cfg, init, whole in (
            (dense, llama.init_params, llama.forward_with_cache),
            (CFG, glm_dsa.init_params, glm_dsa.forward_with_cache)):
        model = served_model(cfg)
        params = init(cfg, jax.random.PRNGKey(0))
        want, _ = whole(params, tokens, cfg, model.init_cache(cfg, 2, 16),
                        start)
        for at in (0, jnp.int32(4)):
            got, cache, counts = model.forward(
                params, tokens, cfg, model.init_cache(cfg, 2, 16), start, at)
            assert got.shape == (2, cfg.vocab_size)
            assert np.allclose(got, want[:, int(at)], atol=1e-6)
            assert jax.tree.structure(cache) == jax.tree.structure(
                model.init_cache(cfg, 2, 16))
            assert all(x.dtype == jnp.int32 and x.shape == ()
                       for x in counts.values())
    assert counts and not served_model(dense).forward(
        llama.init_params(dense, jax.random.PRNGKey(0)), tokens, dense,
        served_model(dense).init_cache(dense, 2, 16), start, 0)[2]
    assert served_model(dense).keys_attended(
        dense, np.array([3, 8, 50])).tolist() == [3, 8, 50]


def test_only_a_config_with_a_cached_forward_is_served():
    assert served_model(CFG).forward is glm_dsa.forward
    assert served_model(CFG).keys_attended(
        CFG, np.array([3, 8, 50])).tolist() == [3, 8, 8]
    with pytest.raises(TypeError, match="MoEConfig"):
        served_model(moe.MoEConfig.debug_moe())


# -- the engine over a cache of eight leaves --------------------------------


@pytest.fixture
def params():
    return families.params(NAME)


def _is_greedy(params, prompt, answer):
    return families.is_greedy(NAME, params, prompt, answer)


def test_the_engine_serves_it_through_the_prefix_cache(params, monkeypatch):
    monkeypatch.setattr(ray_config, "llm_kv_block_tokens", 4)
    monkeypatch.setattr(ray_config, "llm_prefix_shm_tier", False)
    prompt = [int(t) for t in np.random.default_rng(5).integers(
        1, CFG.vocab_size, 21)]  # 5 full blocks and a tail of one
    matched = []

    class Engine(LLMEngine):
        def _prefix_copy_in(self, req, slot, prompt):
            m_tok, chain = super()._prefix_copy_in(req, slot, prompt)
            matched.append(m_tok)
            return m_tok, chain

    engine = Engine(CFG, params, max_batch_size=2, max_seq_len=64)
    leaves = jax.tree.leaves(engine.cache)
    assert len(leaves) == 8
    assert {x.shape[0] for x in leaves} == {1} and \
        sorted({x.shape[3] for x in leaves}) == [8, 16, 32]
    first = engine.generate(prompt, SamplingParams(max_tokens=5))
    second = engine.generate(prompt, SamplingParams(max_tokens=5))
    other = engine.generate(prompt[:9] + prompt[3:12],
                            SamplingParams(max_tokens=5))
    engine.stop()
    totals = engine.metrics()["totals"]
    assert first == second and len(first) == len(other) == 5
    assert _is_greedy(params, prompt, first)
    assert _is_greedy(params, prompt[:9] + prompt[3:12], other)
    assert matched == [0, 20, 8]  # whole blocks, one token left to prefill
    assert totals["kv_blocks_read_back"] == 5 + 0 + 2
    per_token = (3 * (32 + 8) + 2 * 16) * 4
    assert engine._block_nbytes == 4 * per_token
    assert all(len(block) == 8 and all(
        b.shape == (1, 4) + x.shape[3:] for b, x in zip(block, leaves))
        for block in engine._kv_store.values())
    # The expert layers' counters ride the decode blocks.
    # (two slots, two sparse layers, two experts a token; a block still
    # in flight at the stop is never fetched)
    assert totals["decode_steps"] * 8 - 8 <= totals["pairs_routed"] \
        <= totals["decode_steps"] * 8 and totals["pairs_routed"] % 8 == 0
    assert 0 < totals["pairs_held"] < totals["pairs_routed"]
    assert totals["pair_overflows"] == 0
    assert 0 < totals["keys_attended"] < totals["keys_cached"]
