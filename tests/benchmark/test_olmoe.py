"""OLMoE as files: the program's sparse-expert decoder against the plain
reference `references/olmoe.py` at debug widths on the CPU in float32
(logits, load-balancing loss, loss, gradients; both gate conventions;
the q/k norm on and off), the faults that comparison has to catch,
`flops/olmoe.py` by hand, and the reader of the grouped matmuls'
roofline on a hand-made trace."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.flops import olmoe as flops
from benchmark.harness import device as hw
from benchmark.harness import spans as sp
from benchmark.harness.manifest import Cell, model_adapter
from benchmark.readers import grouped_matmul_roofline
from benchmark.references import olmoe as reference
from ray_tpu.models import moe

CONFIG = {"family": "olmoe", "vocab_size": 512, "hidden_size": 64,
          "intermediate_size": 32, "num_hidden_layers": 2,
          "num_attention_heads": 4, "num_key_value_heads": 4,
          "max_position_embeddings": 128, "rope_theta": 10000,
          "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
          "torch_dtype": "float32", "num_experts": 8,
          "num_experts_per_tok": 3, "norm_topk_prob": False,
          "router_aux_loss_coef": 0.01}
# Largest error over largest |logit| (or |gradient|): float32 on both
# sides leaves 2e-7 to 6e-7 at these widths, and the mildest fault
# below (gates rounded to bfloat16) moves the logits by 2.7e-5, the
# others by 4e-4 to 1.
TOLERANCE = 4e-6


def program_config(config, **changes):
    cfg = model_adapter(config).program_config(config)
    return dataclasses.replace(cfg, remat=False, attention="reference",
                               **changes)


def seeded(cfg, seed=0, skew=None):
    """Parameters with norm weights away from one, a router sharp
    enough that the gates differ, and a batch."""
    params = moe.init_moe_params(cfg, jax.random.PRNGKey(seed))
    layers = params["layers"]
    for i, name in enumerate(n for n in ("q_norm", "k_norm") if n in layers):
        layers[name] = 1 + 0.3 * jax.random.normal(
            jax.random.PRNGKey(10 + i), layers[name].shape)
    layers["router"] = layers["router"] * 20
    if skew is not None:
        # Every token's first choice is expert `skew`: the embeddings
        # share a large component that its router column points along.
        params["embed"] = params["embed"] + 1.0
        layers["router"] = layers["router"].at[:, :, skew].add(5.0)
    tokens = jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (2, 41), dtype=np.int32))
    return params, {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}


def distance(got, want):
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


@pytest.mark.parametrize("norm_topk", [False, True])
@pytest.mark.parametrize("qk_norm", [True, False])
def test_program_matches_the_reference(norm_topk, qk_norm):
    config = {**CONFIG, "norm_topk_prob": norm_topk}
    cfg = program_config(config, qk_norm=qk_norm)
    params, batch = seeded(cfg)
    assert ("q_norm" in params["layers"]) == qk_norm
    hp = reference.hyper(config)
    want, want_aux = reference.forward(params, batch["tokens"], hp)
    got, got_aux = moe.moe_forward(params, batch["tokens"], cfg)
    assert distance(got, want) < TOLERANCE
    assert float(got_aux) == pytest.approx(float(want_aux), rel=1e-5)
    # Three of eight experts a token: 3 when every expert is used alike.
    assert float(want_aux) >= 3.0 - 1e-5

    def ours(p):
        return moe.moe_loss_fn(p, batch, cfg)[0]

    def theirs(p):
        return reference.loss(p, batch["tokens"], batch["targets"], hp)

    assert float(ours(params)) == pytest.approx(float(theirs(params)),
                                                rel=1e-5)
    got_g, want_g = jax.grad(ours)(params), jax.grad(theirs)(params)
    for name in ("router", "we1", "we2", "we3", "wq"):
        assert distance(got_g["layers"][name],
                        want_g["layers"][name]) < TOLERANCE, name
    # One expert's share of the gradient, not only the stack's largest.
    assert distance(got_g["layers"]["we2"][1, 5],
                    want_g["layers"]["we2"][1, 5]) < TOLERANCE


def test_the_step_counts_the_pairs_routed_to_each_expert():
    cfg = program_config(CONFIG)
    params, batch = seeded(cfg)
    _, metrics = moe.moe_loss_fn(params, batch, cfg)
    counts = np.asarray(metrics["expert_tokens"])
    assert counts.shape == (2, 8) and counts.dtype == np.int32
    assert (counts.sum(1) == 2 * 40 * 3).all()  # no pair is dropped
    attrs = metrics["span_attrs"]  # what the step's dispatch span carries
    assert int(attrs["expert_tokens_max"]) == counts.max()
    assert int(attrs["expert_tokens_mean"]) == 2 * 40 * 3 // 8
    # ... which is what the reference's router chose, layer by layer.
    hp = reference.hyper(CONFIG)
    _, chose, _ = jax.lax.map(
        lambda t: reference.sequence_logits(params, t, hp), batch["tokens"])
    np.testing.assert_array_equal(counts, np.asarray(chose.sum(0)))


def _logits_with(monkeypatch, cfg, params, batch, fault):
    """The program's logits with one fault put into it."""
    if fault == "gates renormalised":
        cfg = dataclasses.replace(cfg, norm_topk_prob=True)
    elif fault == "one expert too few":
        top_k = jax.lax.top_k

        def short(x, k):
            p, i = top_k(x, k)
            return p.at[..., -1].set(0.0), i

        monkeypatch.setattr(moe.lax, "top_k", short)
    elif fault == "gates in bfloat16":
        top_k = jax.lax.top_k

        def rounded(x, k):
            p, i = top_k(x, k)
            return p.astype(jnp.bfloat16).astype(p.dtype), i

        monkeypatch.setattr(moe.lax, "top_k", rounded)
    elif fault == "a capacity that drops pairs":
        grouped = jax.lax.ragged_dot

        def capped(lhs, rhs, group_sizes):
            # Rows past an expert's capacity of twice the mean come
            # back as zeros.
            cap = 2 * lhs.shape[0] // rhs.shape[0]
            start = jnp.cumsum(group_sizes) - group_sizes
            rank = jnp.arange(lhs.shape[0]) - jnp.repeat(
                start, group_sizes, total_repeat_length=lhs.shape[0])
            return grouped(lhs, rhs, group_sizes) * (rank < cap)[:, None]

        monkeypatch.setattr(moe.lax, "ragged_dot", capped)
    elif fault == "no q/k norm":
        cfg = dataclasses.replace(cfg, qk_norm=False)
    elif fault == "q/k norm per head":
        def per_head(x, weight, eps):
            w = weight.reshape(x.shape[-2:])
            return moe.rms_norm_reference(x, jnp.ones_like(w[0]), eps) * w

        monkeypatch.setattr(moe, "_norm_all_heads", per_head)
    else:
        assert fault is None
    return moe.moe_forward(params, batch["tokens"], cfg)[0]


@pytest.mark.parametrize("fault", [
    None, "gates renormalised", "one expert too few", "gates in bfloat16",
    "a capacity that drops pairs", "no q/k norm", "q/k norm per head"])
def test_the_comparison_fails_a_faulty_program(monkeypatch, fault):
    cfg = program_config(CONFIG)
    # For the capacity: a router under which expert 2 is the first
    # choice of (nearly) every token, a third of all pairs.
    params, batch = seeded(
        cfg, skew=2 if fault == "a capacity that drops pairs" else None)
    if fault == "a capacity that drops pairs":
        counts = moe.moe_loss_fn(params, batch, cfg)[1]["expert_tokens"]
        assert int(counts[0, 2]) > 80 // 2  # over half of the 80 tokens
    want, _ = reference.forward(params, batch["tokens"],
                                reference.hyper(CONFIG))
    got = _logits_with(monkeypatch, cfg, params, batch, fault)
    if fault is None:
        assert distance(got, want) < TOLERANCE
    else:
        assert distance(got, want) > 5 * TOLERANCE, fault


def test_the_reference_reads_the_published_keys_and_nothing_of_the_program():
    cell = Cell("train-olmoe-1chip")
    config = cell.config
    hp = reference.hyper(config)
    assert (hp["n_experts"], hp["top_k"], hp["norm_topk"],
            hp["expert_width"], hp["n_heads"], hp["n_kv_heads"],
            hp["head_dim"]) == (64, 8, False, 1024, 16, 16, 128)
    assert hp["aux_coef"] == 0.01 and hp["rope_theta"] == 1e4
    with open(reference.__file__) as f:
        assert "ray_tpu" not in f.read().split('"""', 2)[2]
    # The adapter builds the preset, but for the depth that was cut.
    cfg = model_adapter(config).program_config(config)
    assert dataclasses.replace(cfg, n_layers=16) == \
        moe.MoEConfig.olmoe_1b_7b()
    small = model_adapter(config).debug(config)
    assert small["num_attention_heads"] == small["num_key_value_heads"]
    assert small["num_experts"] >= 8 and small["num_experts_per_tok"] >= 2
    assert small["norm_topk_prob"] is False and config["hidden_size"] == 2048


# -- flops/olmoe.py by hand ----------------------------------------------------

OLMOE = {"hidden_size": 2048, "num_attention_heads": 16,
         "num_key_value_heads": 16, "intermediate_size": 1024,
         "num_hidden_layers": 3, "vocab_size": 50304, "num_experts": 64,
         "num_experts_per_tok": 8}


def test_olmoe_flops_by_hand():
    # A layer: four 2048x2048 attention projections, the 2048x64 router,
    # 8 experts of three 2048x1024 matrices.
    layer = 4 * 2048 * 2048 + 2048 * 64 + 8 * 3 * 2048 * 1024
    assert layer == 67_239_936
    assert flops.matmul_params_per_token(OLMOE) == \
        3 * layer + 2048 * 50304 == 304_742_400
    # Forward: 2 per weight and causal attention 2 * seq * d a layer:
    # 151.3 MFLOP a layer, 206.0 for the head.
    fwd = 2 * 304_742_400 + 3 * 2 * 4096 * 2048
    assert 2 * layer + 2 * 4096 * 2048 == 151_257_088
    assert flops.train_flops_per_token(OLMOE, 4096) == 3 * fwd \
        == 1_979_449_344


def test_grouped_matmul_ops_and_bytes_by_hand():
    ops, nbytes = flops.grouped_matmul_ops_and_bytes(OLMOE, 8192)
    assert ops == 2 * 65536 * 2048 * 1024
    assert nbytes == 2 * (65536 * 2048 + 65536 * 1024 + 64 * 2048 * 1024)
    mixtral = {"hidden_size": 4096, "intermediate_size": 14336,
               "num_local_experts": 8, "num_experts_per_tok": 2}
    assert flops.grouped_matmul_ops_and_bytes(mixtral, 4096) == (
        2 * 8192 * 4096 * 14336,
        2 * (8192 * 4096 + 8192 * 14336 + 8 * 4096 * 14336))
    seconds, bound = flops.least_seconds(ops, nbytes,
                                         hw.peaks("TPU v5 lite"))
    assert bound == "compute" and seconds == pytest.approx(1.395e-3, rel=1e-3)


# -- the reader on a hand-made trace --------------------------------------------

MS = 1_000_000  # ns


def test_grouped_matmul_roofline_of_a_hand_made_trace(monkeypatch, capsys):
    # One run of step_fn: a `while` that encloses a forward grouped
    # product of 2 ms (the compiler's kernel: known by its name, its
    # path holds no scope), 1 ms of the elementwise work between
    # products, a backward product of 3 ms (known by the primitive its
    # path ends in), and 4 ms of attention; 2 ms of dispatch outside
    # the scope; another program's product beside it.
    names = {
        (77, "ragged-dot-none.1"): "ragged-dot-none",
        (77, "fusion.2"): "jit(step_fn)/jvp(mlp)/expert_matmul/mul:",
        (77, "custom-call.3"): "jit(step_fn)/transpose(jvp(mlp))/"
                               "expert_matmul/ragged_dot_general:",
        (77, "fusion.4"): "jit(step_fn)/jvp(attn)/dot_general:",
        (77, "gather.5"): "jit(step_fn)/jvp(mlp)/moe_dispatch/gather:",
        (88, "ragged-dot-none.1"): "ragged-dot-none",
    }
    events = {"devices": {"/device:TPU:0": {
        "ops": [["while.9", 0, 12 * MS], ["ragged-dot-none.1", 0, 2 * MS],
                ["fusion.2", 2 * MS, 1 * MS],
                ["custom-call.3", 3 * MS, 3 * MS],
                ["fusion.4", 6 * MS, 4 * MS], ["gather.5", 10 * MS, 2 * MS],
                ["ragged-dot-none.1", 50 * MS, 5 * MS]],
        "modules": [["jit_step_fn(77)", 0, 12 * MS],
                    ["jit_other(88)", 50 * MS, 5 * MS]]}}, "host": {}}
    seen = dict(program="step_fn", scope="expert_matmul",
                products=["ragged-dot-none", "ragged_dot_general"])
    assert grouped_matmul_roofline.products_seen(events, names, **seen) == (
        2, pytest.approx(0.006))
    args = dict(seen, flops="olmoe")
    monkeypatch.setattr(sp, "xplane_path", lambda ctx: "unused")
    monkeypatch.setattr(sp, "op_names", lambda path: names)
    cell = Cell("train-olmoe-1chip")
    ctx = {"trace": events, "cell": cell,
           "run": {"batch": 2, "seq": 4096},
           "device": {"count": 1, "peaks": hw.peaks("TPU v5 lite")}}
    # Two products of 1.395 ms at the least in 6 ms of device time.
    assert grouped_matmul_roofline.read(ctx, **args) == pytest.approx(
        100 * 2 * 1.3953e-3 / 0.006, rel=1e-3)
    assert "compute-bound" in capsys.readouterr().out
    # The cell's own metric files name the compiler's kernel: by name
    # for the roofline, as a token of its path for the expert layer's
    # share of the step.
    assert "ragged-dot-none" in {
        m["name"]: m for m in cell.metrics["per_layer"]
    }["kernel.grouped_matmul_roofline"]["args"]["products"]
    assert sp.scope_tokens("ragged-dot-none") == {"ragged-dot-none"}
    # Mixtral runs the same kernel: 8,192 pairs a chip (one sequence of
    # 4096 on each of 4 chips, 2 experts a token) x 4096 x 14336, which
    # its own `flops` file has no count of.
    mixtral = {**ctx, "cell": Cell("train-moe-fsdp4"),
               "run": {"batch": 4, "seq": 4096},
               "device": {**ctx["device"], "count": 4}}
    assert grouped_matmul_roofline.read(mixtral, **args) == pytest.approx(
        100 * 2 * 4.8837e-3 / 0.006, rel=1e-3)
    # The parent's program (no such op), no trace: nothing.
    assert grouped_matmul_roofline.read(
        ctx, **{**args, "products": ["no_such_op"]}) is None
    assert grouped_matmul_roofline.read({"trace": None}, **args) is None
