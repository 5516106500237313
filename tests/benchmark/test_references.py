"""Both plain references against the program at debug widths on the
CPU, in float32: the full forward pass, the training loss, and prefill
plus decode through the cache as the serving runner checks it."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness.manifest import model_adapter
from benchmark.references import dense_decoder, moe_top2
from benchmark.runners import serve as serve_runner
from ray_tpu.models import forward, init_params, loss_fn
from ray_tpu.models.moe import init_moe_params, moe_forward, moe_loss_fn

DENSE = {"family": "dense", "vocab_size": 512, "hidden_size": 64,
         "intermediate_size": 128, "num_hidden_layers": 2,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "max_position_embeddings": 128, "rope_theta": 1e6,
         "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
         "torch_dtype": "float32"}
MOE = {**DENSE, "family": "moe", "num_local_experts": 4,
       "num_experts_per_tok": 2, "router_aux_loss_coef": 0.02}


def model_config(config):
    return model_adapter(config).program_config(config)


def _tokens(cfg, shape=(2, 33)):
    return jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, shape, dtype=np.int32))


@pytest.mark.parametrize("tied", [False, True])
def test_dense_reference_matches_forward_and_loss(tied):
    config = {**DENSE, "tie_word_embeddings": tied}
    cfg = dataclasses.replace(model_config(config), remat=False,
                              attention="reference")
    params = init_params(cfg, jax.random.PRNGKey(1))
    tokens = _tokens(cfg)
    hp = dense_decoder.hyper(config)
    want = dense_decoder.forward(params, tokens[:, :-1], hp)
    got = forward(params, tokens[:, :-1], cfg)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)
    batch = {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}
    loss, _ = loss_fn(params, batch, cfg)
    assert float(loss) == pytest.approx(float(dense_decoder.loss(
        params, batch["tokens"], batch["targets"], hp)), rel=1e-5)


def test_moe_reference_matches_forward_and_loss():
    cfg = dataclasses.replace(model_config(MOE), remat=False,
                              attention="reference")
    params = init_moe_params(cfg, jax.random.PRNGKey(2))
    tokens = _tokens(cfg)
    hp = moe_top2.hyper(MOE)
    want, want_aux = moe_top2.forward(params, tokens[:, :-1], hp)
    got, got_aux = moe_forward(params, tokens[:, :-1], cfg)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)
    assert float(got_aux) == pytest.approx(float(want_aux), rel=1e-5)
    # Two of four experts a token, uniform at worst: the loss is >= 1.
    assert float(want_aux) >= 1.0 - 1e-6
    batch = {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}
    loss, _ = moe_loss_fn(params, batch, cfg)
    assert float(loss) == pytest.approx(float(moe_top2.loss(
        params, batch["tokens"], batch["targets"], hp)), rel=1e-5)


def test_moe_reference_uses_only_the_chosen_experts():
    cfg = dataclasses.replace(model_config(MOE), remat=False)
    params = init_moe_params(cfg, jax.random.PRNGKey(3))
    lp = jax.tree.map(lambda x: x[0], params["layers"])
    hp = moe_top2.hyper(MOE)
    h = jax.random.normal(jax.random.PRNGKey(4), (5, 64))
    out, chose, prob = moe_top2.experts(h, lp, hp)
    # By hand for one token: softmax, best two, renormalised.
    probs = jax.nn.softmax(h[0] @ lp["router"])
    best = np.argsort(-np.asarray(probs))[:2]
    want = sum(
        probs[e] / probs[best].sum() * dense_decoder.feed_forward(
            h[0], {"w1": lp["we1"][e], "w3": lp["we3"][e],
                   "w2": lp["we2"][e]}) for e in best)
    np.testing.assert_allclose(out[0], want, atol=1e-6, rtol=1e-5)
    # Five tokens chose two experts each; probabilities sum to one.
    assert float(chose.sum()) == 10.0
    assert float(prob.sum()) == pytest.approx(5.0, rel=1e-5)


# Hidden 512 and heads of 64 give the query-key scores the spread they
# have at the published widths (0.02^2 x hidden x sqrt(head) = 1.6), so
# attention weighs in the logits; at hidden 64 it is nearly uniform.
SERVED = {**DENSE, "hidden_size": 512, "intermediate_size": 1024,
          "num_attention_heads": 8, "reference": "dense_decoder", "serve": {
    "max_seq_len": 128, "reference_layers": 2,
    "reference_prompt_lens": [96, 64, 33, 9], "reference_decode_steps": 3,
    "logit_tolerance": 0.015,
    "probe_prompt_lens": [9, 7, 5, 3], "probe_total": 12,
    "served_token_margin": 0.015}}


def test_prefill_and_decode_through_the_cache_match_the_reference():
    err, positions = serve_runner.check_against_reference(
        SERVED, seed=2 ** 31 + 9)
    assert positions == 4 * 99 and err < 1e-4


def _faulty(fault):
    """`forward_with_cache` with one fault of the kind the next
    rewrites of `_cached_attention` could make."""
    from ray_tpu.models import llama

    def served(params, tokens, cfg, cache, start_pos):
        decode = tokens.shape[1] == 1
        if fault == "fp8 weights":
            params = jax.tree.map(
                lambda w: w.astype(jnp.float8_e4m3fn).astype(w.dtype)
                if w.ndim > 1 else w, params)
        elif fault == "no attention":
            params = {**params, "layers": {
                **params["layers"],
                "wo": jnp.zeros_like(params["layers"]["wo"])}}
        elif fault == "rows mixed up" and decode:
            start_pos = start_pos[::-1]
        elif fault == "position off by one" and decode:
            start_pos = start_pos + 1
        elif fault == "rope theta":
            cfg = dataclasses.replace(cfg, rope_theta=1e4)
        if fault == "no mask" and decode:
            # Every row sees all that the cache holds.
            start_pos_seen = jnp.full_like(start_pos, cache["k"].shape[2] - 1)
            real = llama._cached_attention
            llama._cached_attention = lambda c, q, k, v, pos: real(
                c, q, k, v, jnp.broadcast_to(start_pos_seen[:, None],
                                             pos.shape))
            try:
                return llama.forward_with_cache(params, tokens, cfg, cache,
                                                start_pos)
            finally:
                llama._cached_attention = real
        return llama.forward_with_cache(params, tokens, cfg, cache,
                                        start_pos)

    return served


@pytest.mark.parametrize("fault", [
    "fp8 weights", "no attention", "rows mixed up", "position off by one",
    "no mask", "rope theta"])
def test_the_logit_check_fails_a_faulty_served_path(fault):
    err, _ = serve_runner.check_against_reference(
        SERVED, seed=5, served=_faulty(fault))
    assert err > SERVED["serve"]["logit_tolerance"], (fault, err)


def test_served_tokens_are_held_against_the_reference():
    cfg = model_config(SERVED)
    params = init_params(cfg, jax.random.PRNGKey(6))
    asked = serve_runner.probes(SERVED, 11)
    assert [len(b["prompt_ids"]) + b["max_tokens"] for b in asked] == [12] * 4

    def greedy(body):
        seq = list(body["prompt_ids"])
        for _ in range(body["max_tokens"]):
            logits = forward(params, jnp.asarray([seq]), dataclasses.replace(
                cfg, remat=False, attention="reference"))
            seq.append(int(logits[0, -1].argmax()))
        return seq[len(body["prompt_ids"]):]

    answers = [greedy(b) for b in asked]
    short, same, n = serve_runner.check_served_tokens(
        SERVED, params, asked, answers)
    assert n == 3 + 5 + 7 + 9 and same == 1.0 and short == 0.0
    # Two requests' answers swapped (slots mixed up): same lengths, so
    # swap the middle two after cutting both to the shorter.
    wrong = [list(a) for a in answers]
    wrong[1][:5], wrong[2][:5] = answers[2][:5], answers[1][:5]
    short, same, _ = serve_runner.check_served_tokens(
        SERVED, params, asked, wrong)
    assert short > SERVED["serve"]["served_token_margin"] and same < 1.0
    # An answer that is short, or an error instead of one, fails outright.
    short, _, n = serve_runner.check_served_tokens(
        SERVED, params, asked, [answers[0][:-1]] + answers[1:])
    assert short == float("inf") and n == 0
    short, _, _ = serve_runner.check_served_tokens(
        SERVED, params, asked, ["RuntimeError('status 500')"] + answers[1:])
    assert short == float("inf")
