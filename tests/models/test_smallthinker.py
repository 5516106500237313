"""SmallThinker's trained stack against the plain reference
`benchmark/references/smallthinker.py` at a small size on the CPU in
float32: logits, loss and every gradient leaf, whole and as a held
share; the faults that comparison has to catch; the parameter counts at
the published depth and at the benchmark's cut, from shapes; the four
shares of a layer adding up to the whole; a crowded share's further
buffers; and what the step hands the tracing."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import smallthinker as reference
from ray_tpu.models import (init_train_state, llama, make_optimizer,
                            make_train_step, moe)
from ray_tpu.models import smallthinker as st
from ray_tpu.parallel import MeshConfig, create_mesh
from tests.models.test_moe import grouped_products

# Largest error over largest |value|: float32 on both sides leaves 2e-7
# to 6e-7 at these widths; the mildest fault below moves the logits by
# 3e-4.
TOLERANCE = 4e-6


def config(held=(0, 4), **changes):
    cfg = dataclasses.replace(st.SmallThinkerConfig.debug(),
                              attention="reference", sliding_window=12,
                              experts_held=held)
    return dataclasses.replace(cfg, **changes)


def hyper(cfg):
    """What the reference reads of a configuration file, from the
    program's config."""
    return {"n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
            "head_dim": cfg.head_dim, "rope_theta": cfg.rope_theta,
            "norm_eps": cfg.norm_eps, "tied": False,
            "window": cfg.sliding_window,
            "layers": tuple((kind == "window",) * 2
                            for kind in cfg.layer_kinds),
            "n_experts": cfg.n_experts,
            "held": cfg.experts_held or (0, cfg.n_experts),
            "top_k": cfg.n_experts_per_token,
            "expert_width": cfg.hidden_dim, "aux_coef": cfg.aux_loss_coeff}


def seeded(cfg, seed=0, seq=40):
    """Parameters with norm weights away from one and a router sharp
    enough that the gates differ, and a batch longer than three
    windows."""
    params = st.init_params(cfg, jax.random.PRNGKey(seed))
    for i, run in enumerate(params["runs"]):
        run["router"] = run["router"] * 20
        for j, name in enumerate(("attn_norm", "mlp_norm")):
            run[name] = 1 + 0.3 * jax.random.normal(
                jax.random.PRNGKey(10 + 2 * i + j), run[name].shape)
    tokens = jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (2, seq + 1), dtype=np.int32))
    return params, {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}


def distance(got, want):
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


@pytest.mark.parametrize("held", [None, (0, 4), (6, 2)])
def test_program_matches_the_reference(held):
    cfg = config(held)
    params, batch = seeded(cfg)
    hp = hyper(cfg)
    want, want_aux = reference.forward(params, batch["tokens"], hp)
    got, got_aux, counts = st.forward(params, batch["tokens"], cfg)
    assert distance(got, want) < TOLERANCE
    assert float(got_aux) == pytest.approx(float(want_aux), rel=1e-5)
    assert counts.shape == (4, 8) and int(counts.sum()) == 4 * 2 * 40 * 3

    loss, metrics = st.loss_fn(params, batch, cfg)
    want_loss = reference.loss(params, batch["tokens"], batch["targets"], hp)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    attrs = {k: int(v) for k, v in metrics["span_attrs"].items()}
    first, count = cfg.experts_held or (0, 8)
    share = np.asarray(counts)[:, first:first + count]
    assert attrs["pairs_routed"] == 4 * 2 * 40 * 3
    assert attrs["pairs_held"] == share.sum()
    assert attrs["experts_touched"] == (share > 0).sum()
    assert attrs["expert_tokens_max"] == share.max()
    assert attrs["expert_tokens_mean"] == share.sum() // share.size
    assert attrs["pair_overflows"] == 0


@pytest.mark.parametrize("axes", [dict(data=1),
                                  dict(data=2, fsdp=2, tensor=2)],
                         ids=["one_device_mesh", "fsdp"])
def test_all_the_experts_on_a_mesh_count_as_on_one_device(axes):
    """With no held share (`experts_held=None`) the layer goes through
    `moe._moe_ffn`'s `shard_map`, on a mesh of one device as the runner
    builds it and on chips that share the experts' hidden width: the
    loss and what the step hands the tracing are the one device's, the
    overflow count an int32 0 a layer."""
    cfg = config(None)
    params, batch = seeded(cfg)
    batch = {name: jnp.concatenate([rows, rows[::-1]])
             for name, rows in batch.items()}
    want, metrics = st.loss_fn(params, batch, cfg)
    mesh = create_mesh(MeshConfig(**axes),
                       devices=jax.devices()[:math.prod(axes.values())])
    got, on_mesh = jax.jit(
        lambda p, b: st.loss_fn(p, b, cfg, mesh=mesh))(params, batch)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    attrs = {k: int(v) for k, v in on_mesh["span_attrs"].items()}
    assert attrs == {k: int(v) for k, v in metrics["span_attrs"].items()}
    assert attrs["pair_overflows"] == 0
    assert attrs["pairs_held"] == attrs["pairs_routed"] == 4 * 4 * 40 * 3


@pytest.mark.parametrize("held", [None, (2, 4)])
def test_every_gradient_leaf_matches_the_reference(held):
    cfg = config(held)
    params, batch = seeded(cfg, seed=1)
    hp = hyper(cfg)
    got = jax.grad(lambda p: st.loss_fn(p, batch, cfg)[0])(params)
    want = jax.grad(lambda p: reference.loss(
        p, batch["tokens"], batch["targets"], hp))(params)
    leaves = jax.tree_util.tree_leaves_with_path(got)
    assert len(leaves) == 3 + 2 * 10  # ten leaves a layer, two runs
    for (path, a), b in zip(leaves, jax.tree.leaves(want)):
        assert float(jnp.abs(b).max()) > 0, path
        assert distance(a, b) < TOLERANCE, jax.tree_util.keystr(path)


def test_remat_changes_no_number():
    cfg = config()
    params, batch = seeded(cfg)
    plain = jax.value_and_grad(lambda p: st.loss_fn(p, batch, cfg)[0])(params)
    remat = jax.value_and_grad(lambda p: st.loss_fn(
        p, batch, dataclasses.replace(cfg, remat=True))[0])(params)
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(remat)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("held", [None, (0, 4)])
def test_a_rematerialised_layer_keeps_nothing(held):
    """This family's rematerialised layer saves its input alone (its
    cell has no memory for more, `loss_fn`): with `remat` on, each of
    the two runs' layer bodies computes its three forward products again
    before the six backward ones, 12 a run where a layer that keeps
    everything has 9. The products carry `moe._SAVED`'s names, which a
    list here would honour: this count moves if one ever reaches it."""
    cfg = config(held)
    params, batch = seeded(cfg)

    def products(cfg):
        return grouped_products(lambda p: st.loss_fn(p, batch, cfg)[0],
                                params)

    assert products(cfg) == 2 * 9
    assert products(dataclasses.replace(cfg, remat=True)) == 2 * 12


# -- the faults ---------------------------------------------------------------


def cut_mantissa(params, bits=20):
    """float32 cut to float8 e4m3's three mantissa bits."""
    def cut(x):
        if x.ndim < 2:
            return x
        u = jax.lax.bitcast_convert_type(x, jnp.uint32)
        u = (u + jnp.uint32(1 << (bits - 1))) & ~jnp.uint32((1 << bits) - 1)
        return jax.lax.bitcast_convert_type(u, jnp.float32)
    return jax.tree.map(cut, params)


def turned_always(monkeypatch, turned):
    real = llama.self_attention
    monkeypatch.setattr(st.llama, "self_attention", lambda *a, **kw: real(
        *a, **{**kw, "turned": turned}))


def router_behind_the_norm(monkeypatch):
    real = moe._moe_ffn
    monkeypatch.setattr(st.moe, "_moe_ffn", lambda *a, **kw: real(
        *a, **{**kw, "routed": None}))


def drop_a_held_pair(monkeypatch):
    real = moe._held_experts_trained

    def dropping(cfg, x, gates, top_i, *ws):
        first, count = cfg.experts_held
        held = (top_i >= first) & (top_i < first + count)
        at = jnp.argmax(held.reshape(-1))
        return real(cfg, x, gates.reshape(-1).at[at].set(0.0).reshape(
            gates.shape), top_i, *ws)

    monkeypatch.setattr(st.moe, "_held_experts_trained", dropping)


FAULTS = {
    "weights cut to float8's mantissa": dict(params=cut_mantissa),
    "a window of one key less": dict(cfg=dict(sliding_window=11)),
    "the rotary turn on the full layer": dict(
        patch=lambda mp: turned_always(mp, True)),
    "no rotary turn on a windowed layer": dict(
        patch=lambda mp: turned_always(mp, False)),
    "the router fed the normed post-attention stream": dict(
        patch=router_behind_the_norm),
    "silu in place of relu": dict(cfg=dict(expert_kind="swiglu")),
    "one expert a token fewer": dict(cfg=dict(n_experts_per_token=2)),
    "a pair on a held expert dropped": dict(patch=drop_a_held_pair),
}


@pytest.fixture(scope="module")
def wanted():
    """The reference's logits of the seeded model, computed once for
    the faults below."""
    cfg = config()
    params, batch = seeded(cfg)
    return params, batch, reference.forward(params, batch["tokens"],
                                            hyper(cfg))[0]


@pytest.mark.parametrize("fault", FAULTS)
def test_the_comparison_fails_a_fault(fault, wanted, monkeypatch):
    cfg = config()
    params, batch, want = wanted
    how = FAULTS[fault]
    if "patch" in how:
        how["patch"](monkeypatch)
    got, _, _ = st.forward(
        how.get("params", lambda p: p)(params), batch["tokens"],
        dataclasses.replace(cfg, **how.get("cfg", {})))
    assert distance(got, want) > 50 * TOLERANCE, fault


# -- sizes --------------------------------------------------------------------


def count_from_shapes(cfg):
    shapes = jax.eval_shape(lambda k: st.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    return sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes))


def test_parameter_counts_from_shapes():
    published = st.SmallThinkerConfig()
    assert published.runs() == [("full", 1), ("window", 3)] * 13
    assert count_from_shapes(published) == published.num_params() \
        == 21_506_562_560
    # One layer outside its experts, one expert, embedding and head.
    assert 2 * 2560 + 2 * 2560 * 28 * 128 + 2 * 2560 * 4 * 128 \
        + 2560 * 64 == 21_140_480
    assert 3 * 2560 * 768 == 5_898_240
    assert 52 * (21_140_480 + 64 * 5_898_240) + 2 * 151_936 * 2560 + 2560 \
        == 21_506_562_560
    cut = dataclasses.replace(
        published, n_layers=4, layer_kinds=st.PUBLISHED_LAYER_KINDS[:4],
        experts_held=(0, 16), vocab_size=37_984)
    assert count_from_shapes(cut) == cut.num_params() == 656_529_920
    assert cut.head_dim == 128 != cut.dim // cut.n_heads


# -- a share ------------------------------------------------------------------


def expert_layer(cfg, seed=0, skew=None):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    whole = dataclasses.replace(cfg, experts_held=None)
    lp = moe.expert_init(whole, keys[:4])
    lp["router"] = lp["router"] * 20
    if skew is not None:
        lp["router"] = lp["router"].at[:, skew].add(0.5)
    h = jax.random.normal(keys[4], (2, 24, cfg.dim))
    stream = jax.random.normal(keys[5], (2, 24, cfg.dim)) \
        + (0 if skew is None else 1.0)
    return whole, lp, h, stream


def share_of(lp, first, count):
    return {name: leaf[first:first + count] if name.startswith("we")
            else leaf for name, leaf in lp.items()}


def test_the_four_shares_add_up_to_the_whole_layer():
    """The guide's share test: a layer's experts divided over four
    chips, two each; every chip routes over all eight, computes its own
    experts' part for the pairs that fell on them, and the parts add up
    to the uncut layer's output. Nothing is computed alike on every
    chip (the model has no shared expert), so nothing is counted
    twice."""
    whole, lp, h, stream = expert_layer(config())
    want, aux, counts, share = moe._moe_ffn(
        whole, lp, h, None, None, routed=stream, trained=True)
    total, held = jnp.zeros_like(want), 0
    for first in range(0, 8, 2):
        cfg = dataclasses.replace(whole, experts_held=(first, 2))
        out, part_aux, part_counts, part = moe._moe_ffn(
            cfg, share_of(lp, first, 2), h, None, None, routed=stream,
            trained=True)
        total += out
        held += int(part["pairs_held"])
        # Router, load-balancing loss and counts are every chip's alike.
        assert float(part_aux) == float(aux)
        assert np.array_equal(part_counts, counts)
        assert int(part["pairs_held"]) == int(counts[first:first + 2].sum())
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-6)
    assert held == int(share["pairs_routed"]) == 2 * 24 * 3
    # And the router did read the other input.
    other, *_ = moe._moe_ffn(whole, lp, h, None, None, trained=True)
    assert distance(other, want) > 0.1


def test_a_crowded_share_takes_further_buffers_and_drops_nothing(
        monkeypatch):
    """Every token's first choice falls on the share: with buffers of
    16 rows its 48 to 70 pairs take several, under `lax.cond`, and
    output and gradients are those of one buffer that holds them all."""
    cfg = dataclasses.replace(config(), experts_held=(2, 2))
    _, lp, h, stream = expert_layer(cfg, seed=3, skew=3)
    lp = share_of(lp, 2, 2)

    def run(lp, h, stream):
        out, _, _, share = moe._moe_ffn(cfg, lp, h, None, None,
                                        routed=stream, trained=True)
        return (out * jnp.cos(jnp.arange(out.size).reshape(out.shape))
                ).sum(), (out, share)

    (_, (want, share)), want_grads = jax.value_and_grad(
        run, argnums=(0, 1, 2), has_aux=True)(lp, h, stream)
    assert int(share["pair_overflows"]) == 0
    assert int(share["pairs_held"]) >= 48
    monkeypatch.setattr(moe, "_HELD_ROWS_MIN", 16)
    monkeypatch.setattr(moe, "_TRAINED_ROWS_SLACK", 0.1)
    (_, (got, crowded)), got_grads = jax.value_and_grad(
        run, argnums=(0, 1, 2), has_aux=True)(lp, h, stream)
    assert int(crowded["pair_overflows"]) \
        == -(-int(share["pairs_held"]) // 16) - 1 >= 2
    assert int(crowded["pairs_held"]) == int(share["pairs_held"])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


# -- what the tracing is handed ------------------------------------------------


def test_scopes_of_the_lowered_step():
    cfg = config()
    params, batch = seeded(cfg)
    text = jax.jit(jax.grad(lambda p: st.loss_fn(p, batch, cfg)[0])).lower(
        params).as_text(debug_info=True)
    for scope in ("attn/window/", "mlp/router", "mlp/moe_dispatch",
                  "mlp/expert_matmul", "mlp/moe_combine", "loss"):
        assert scope in text, scope
    # The backward pass keeps the paths (`add_any` is a cotangent's).
    assert "attn/window/add_any" in text


def test_step_dispatch_span_carries_the_shares_counts():
    from ray_tpu._private import critical_path, flight_recorder

    critical_path.reset()
    flight_recorder.reset()
    cfg = config()
    mesh = create_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    params = st.init_params_sharded(cfg, mesh, jax.random.PRNGKey(0))
    tx = make_optimizer(1e-3, warmup_steps=0)
    step = make_train_step(
        lambda p, b: st.loss_fn(p, b, cfg, mesh=mesh), tx, mesh=mesh)
    state = init_train_state(params, tx)
    _, batch = seeded(cfg)
    seen = []
    for _ in range(3):
        state, metrics = step(state, batch)
        seen.append(metrics)
        jax.block_until_ready(metrics)
    assert float(seen[2]["loss"]) < float(seen[0]["loss"])
    critical_path.flush()
    spans = [s for s in flight_recorder.local_snapshot()["spans"]
             if s["stage"] == "train.step_dispatch"]
    critical_path.reset()
    flight_recorder.reset()
    assert len(spans) == 3 and "attrs" not in spans[0]
    for span, before in zip(spans[1:], seen):
        assert span["attrs"] == {k: int(v) for k, v in
                                 before["span_attrs"].items()}
        assert span["attrs"]["pairs_routed"] == 4 * 2 * 40 * 3
        assert 0 < span["attrs"]["pairs_held"] < 4 * 2 * 40 * 3
