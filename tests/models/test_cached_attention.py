"""`_cached_attention` against the plain repeat-and-float32 form it
replaced (kept here as the reference); a walk over the jaxpr of both
served families' cached forward pass that holds its structure: the slot
cache rides the layer scan as its carry, a layer writes B x T rows into
it, and nothing but the stacks is larger than a layer's leaf (so the
repeat and the up-cast of the cache cannot come back unnoticed); and
the forward pass against the form it had up to PR 32 (kept here too:
the cache scanned beside the parameters, a layer's slices written
whole), to the bit; and a decode step through
`ops.attention.decode_attention`, the kernel a TPU runs it through,
interpreted here, against the plain path at the logits."""

import dataclasses
import functools
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import decoder, glm_dsa, llama
from ray_tpu.ops import attention
from ray_tpu.ops.norms import layer_norm, rms_norm_reference
from ray_tpu.models.llama import (
    LlamaConfig,
    forward_with_cache,
    init_kv_cache,
    init_params,
)

S = 96
HEAD_DIM = 64
KV_HEADS = 2


def reference_attention(cfg, q, k_cache, v_cache, q_positions):
    """The form `_cached_attention` had up to PR 24: K and V repeated
    over the GQA group and multiplied in float32."""
    d = q.shape[-1]
    rep = cfg.n_heads // cfg.n_kv_heads
    k = jnp.repeat(k_cache, rep, axis=2)
    v = jnp.repeat(v_cache, rep, axis=2)
    scores = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32),
                        k.astype(jnp.float32),
                        preferred_element_type=jnp.float32) * (d ** -0.5)
    mask = jnp.arange(k.shape[1])[None, None, :] <= q_positions[:, :, None]
    scores = jnp.where(mask[:, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhts,bshd->bthd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def _starts(b, t):
    """Rows at different positions: the first at 0, the second ending at
    S - 1, the others in between. One row alone takes each in turn."""
    rows = [0, S - t, 5, 17][:b]
    return [rows] if b > 1 else [[0], [S - t], [11]]


@pytest.mark.parametrize("b,t", [(4, 1), (1, 64), (3, 16)])
@pytest.mark.parametrize("rep", [1, 4, 8])
def test_matches_repeat_and_float32_reference(rep, b, t):
    cfg = LlamaConfig(n_heads=KV_HEADS * rep, n_kv_heads=KV_HEADS,
                      dim=KV_HEADS * rep * HEAD_DIM)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(rep * 100 + b), 3)
    q = jax.random.normal(kq, (b, t, cfg.n_heads, HEAD_DIM), jnp.bfloat16)
    # Every position of every row holds keys and values, as a reused
    # slot's region does beyond the row's own length.
    shape = (b, S, KV_HEADS, HEAD_DIM)
    k_cache = jax.random.normal(kk, shape, jnp.bfloat16)
    v_cache = jax.random.normal(kv, shape, jnp.bfloat16)
    for starts in _starts(b, t):
        positions = (jnp.asarray(starts, jnp.int32)[:, None]
                     + jnp.arange(t)[None, :])
        got = llama._cached_attention(cfg, q, k_cache, v_cache, positions)
        want = reference_attention(cfg, q, k_cache, v_cache, positions)
        assert got.shape == want.shape and got.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=1e-2, atol=1e-2, err_msg=f"starts {starts}")
        # A stale key must weigh nothing: the row at position 0 returns
        # its first value whatever lies behind it.
        if starts[0] == 0:
            np.testing.assert_array_equal(
                np.asarray(got[0, 0], np.float32),
                np.asarray(jnp.repeat(v_cache[0, 0], rep, axis=0),
                           np.float32))


# ---------------------------------------------------------------------------
# The structure of the cached forward pass
# ---------------------------------------------------------------------------

# Longer than a block of keys (`glm_dsa._KEY_BLOCK`), so that a read of
# a block is not a read of a one-layer run's whole leaf.
SLOTS, MAX_SEQ = 4, 2048

# Two layers and more in a run, so that a cache scanned by layer would
# show; bfloat16 and four query heads a KV head for the dense family, so
# that the repeat and the up-cast would.
WALKED = {
    "llama": (
        LlamaConfig(vocab_size=64, dim=512, n_layers=2, n_heads=8,
                    n_kv_heads=2, hidden_dim=64, max_seq_len=MAX_SEQ,
                    dtype=jnp.bfloat16),
        init_params, init_kv_cache, forward_with_cache),
    "glm_dsa": (
        dataclasses.replace(
            glm_dsa.GlmDsaConfig.debug_glm(), n_layers=4, layer_kinds=(
                ("dense", "full"), ("sparse", "shared"),
                ("sparse", "shared"), ("sparse", "full"))),
        glm_dsa.init_params, glm_dsa.init_cache,
        glm_dsa.forward_with_cache),
}


def _eqns(jaxpr):
    """Every equation, those inside scans, loops and calls too."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _walk(family, b, t):
    """The cached forward pass of `tokens` [b, t] as a jaxpr, walked:
    (offences, how many scans carry a cache leaf, how many writes into
    a cache leaf). An offence is a cache leaf scanned in or out by
    layer, a write into one that is not b x t rows, and an intermediate
    larger than a layer's largest leaf that is not a stack, or as large
    and float32 where the cache is not."""
    cfg, init, init_cache, forward = WALKED[family]
    params = jax.eval_shape(lambda: init(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: init_cache(cfg, b, MAX_SEQ))
    closed = jax.make_jaxpr(
        lambda p, c, tok, pos: forward(p, tok, cfg, c, pos))(
            params, cache, jax.ShapeDtypeStruct((b, t), jnp.int32),
            jax.ShapeDtypeStruct((b,), jnp.int32))
    leaves = jax.tree.leaves(cache)
    stacks = {(x.shape, x.dtype) for x in leaves}
    layer_leaf = max(x.size // x.shape[0] for x in leaves)

    def is_stack(var):
        aval = var.aval
        return (getattr(aval, "shape", None), getattr(aval, "dtype", None)) \
            in stacks

    offences, carrying, writes, seen = [], 0, 0, 0
    for eqn in _eqns(closed.jaxpr):
        seen += 1
        name = eqn.primitive.name
        if name == "scan":
            consts, carry = eqn.params["num_consts"], eqn.params["num_carry"]
            carrying += any(map(is_stack,
                                eqn.invars[consts:consts + carry]))
            offences += [("scanned in", v.aval.shape)
                         for v in eqn.invars[consts + carry:] if is_stack(v)]
            offences += [("scanned out", v.aval.shape)
                         for v in eqn.outvars[carry:] if is_stack(v)]
        if list(jax.core.jaxprs_in_params(eqn.params)):
            continue  # a scan, a loop, a call: its body is walked
        for var in eqn.outvars:
            aval = var.aval
            if not hasattr(aval, "shape"):
                continue
            if is_stack(var):
                rows = eqn.invars[2].aval.shape[:2] \
                    if name == "scatter" else None
                writes += rows == (b, t)
                if rows != (b, t):
                    offences.append((name, aval.shape, "rows", rows))
            elif aval.size > layer_leaf or (
                    aval.size == layer_leaf and aval.dtype == jnp.float32
                    and leaves[0].dtype != jnp.float32):
                offences.append((name, aval.shape, aval.dtype.name))
    assert seen > 50  # the walk went into the layer scan
    return offences, carrying, writes, len(leaves)


@pytest.mark.parametrize("b,t", [(SLOTS, 1), (1, 4)],
                         ids=["decode", "prefill"])
@pytest.mark.parametrize("family", WALKED)
def test_the_cache_rides_the_scan_and_is_written_by_rows(family, b, t):
    offences, carrying, writes, leaves = _walk(family, b, t)
    assert offences == []
    # One scan a run of layers carries that run's leaves, and each leaf
    # is written once in its scan's body.
    runs = len(WALKED[family][0].runs()) if family == "glm_dsa" else 1
    assert carrying == runs
    assert writes == leaves


def test_the_walk_finds_the_repeat_and_the_upcast(monkeypatch):
    monkeypatch.setattr(llama, "_cached_attention", reference_attention)
    found = [o for o in _walk("llama", SLOTS, 1)[0] if len(o) == 3]
    # rep x the cache (bf16, then float32) for K and for V.
    assert len(found) >= 4
    assert any(dtype == "float32" for _, _, dtype in found)
    assert any(dtype == "bfloat16" for _, _, dtype in found)


# ---------------------------------------------------------------------------
# Against the form of up to PR 32, to the bit
# ---------------------------------------------------------------------------


def scanned_layers(mixer, ffn, cfg, rope, x, stacked, state=None,
                   handed=None, **_):
    """`decoder.layers` as it was: the mixer's state scanned in and out
    beside the parameters, a layer's slices to the mixer."""
    def body(carry, scanned):
        x, handed = carry
        lp, layer_state = scanned
        x, layer_state, extras, handed = decoder.block(
            mixer, ffn, cfg, rope, x, lp, layer_state, handed)
        return (x, handed), (layer_state, extras)

    (x, handed), (state, extras) = lax.scan(body, (x, handed),
                                            (stacked, state))
    return x, state, extras, handed


def _write_slices(cache, new, start_pos):
    """cache [B, S, ...] with new [B, T, ...] at each row's `start_pos`."""
    return jax.vmap(lambda c, n, s: lax.dynamic_update_slice(
        c, n.astype(c.dtype), (s,) + (0,) * (c.ndim - 1)))(
            cache, new, start_pos)


def sliced_self_attention(cfg, start_pos, positions):
    """`llama._cached_self_attention` as it was: carries the layer's
    (k_cache, v_cache), each [B, S, Hkv, D]."""
    def mixer(h, lp, rope, state, handed):
        q, k, v = llama._qkv(cfg, h, lp, rope, positions,
                             llama.norm_all_heads)
        k_cache = _write_slices(state[0], k, start_pos)
        v_cache = _write_slices(state[1], v, start_pos)
        return (llama._cached_attention(cfg, q, k_cache, v_cache, positions),
                (k_cache, v_cache), handed)

    return mixer


def sliced_glm_mixer(cfg, indexer, start_pos, positions):
    """`glm_dsa._mixer` as it was: carries the layer's slices (latent,
    rotary key[, indexer's key]), each [B, S, width]. The indexer and
    attention, which now read a stack at a layer, are handed the slice
    as a stack of one."""
    g = glm_dsa
    nope, rot = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    scale = (nope + rot) ** -0.5
    index_scale = (cfg.index_n_heads * cfg.index_head_dim) ** -0.5

    def mixer(h, lp, rope, state, selected):
        cos, sin = rope
        c_q = rms_norm_reference(
            jnp.einsum("btd,dr->btr", h, lp["wqa"]), lp["q_norm"],
            cfg.norm_eps)
        q = jnp.einsum("btr,rhk->bthk", c_q, lp["wqb"])
        kva = jnp.einsum("btd,dc->btc", h, lp["wkva"])
        c_kv = rms_norm_reference(kva[..., :cfg.kv_lora_rank],
                                  lp["kv_norm"], cfg.norm_eps)
        latent = _write_slices(state[0], c_kv, start_pos)
        rope_keys = _write_slices(
            state[1], g._rotate_pairs(kva[..., cfg.kv_lora_rank:], cos,
                                      sin), start_pos)
        q_nope = q[..., :nope].astype(latent.dtype)
        q_rope = g._rotate_pairs(q[..., nope:], cos, sin).astype(
            latent.dtype)
        new_state = (latent, rope_keys)
        if indexer == "full":
            qi = g._rotate_head(
                jnp.einsum("btr,rjd->btjd", c_q, lp["wiq"]), cos, sin, rot)
            ki = g._rotate_head(layer_norm(
                jnp.einsum("btd,de->bte", h, lp["wik"]), lp["ik_norm"],
                lp["ik_bias"], g._INDEX_KEY_EPS), cos, sin, rot)
            index_keys = _write_slices(state[2], ki, start_pos)
            qi = qi.astype(index_keys.dtype)
            w = jnp.einsum("btd,dj->btj", h, lp["wiw"]).astype(
                jnp.float32) * index_scale
            new_state += (index_keys,)

        def attend(q_nope, q_rope, pos, *chosen):
            if indexer == "full":
                mask = g._select(cfg, g._index_scores(
                    *chosen, (index_keys[None], 0), pos), pos)
            else:
                mask, = chosen
            q_lat = jnp.einsum("bthk,chk->bthc", q_nope,
                               lp["wkvb"][..., :nope])
            out = g._attend(q_lat, q_rope, (latent[None], 0),
                            (rope_keys[None], 0), mask, pos, scale)
            out = jnp.einsum("bthc,chv->bthv", out.astype(latent.dtype),
                             lp["wkvb"][..., nope:])
            return out, mask

        chosen = (qi, w) if indexer == "full" else (selected,)
        out, selected = g._by_query_blocks(
            attend, h.shape[1], q_nope, q_rope, positions, *chosen)
        return out, new_state, selected

    return mixer


SERVED = {
    # float32, so that a bit is a bit of the arithmetic; the GLM stack
    # has a run of two `shared` layers above their `full` one, and
    # contexts past its `index_topk` of 8.
    "llama": (LlamaConfig.debug(), init_params, init_kv_cache,
              forward_with_cache, llama, "_cached_self_attention",
              sliced_self_attention),
    "glm_dsa": (WALKED["glm_dsa"][0], glm_dsa.init_params,
                glm_dsa.init_cache, glm_dsa.forward_with_cache, glm_dsa,
                "_mixer", sliced_glm_mixer),
}


@pytest.mark.parametrize("family", SERVED)
def test_logits_and_cache_are_those_of_the_scanned_cache_to_the_bit(
        monkeypatch, family):
    cfg, init, init_cache, forward, module, name, sliced = SERVED[family]
    params = init(cfg, jax.random.PRNGKey(1))
    lens = np.array([13, 11, 9, 12])
    rows, n_pre, n_dec = len(lens), int(lens.max()), 4
    tokens = np.random.default_rng(5).integers(
        0, cfg.vocab_size, (rows, n_pre + n_dec), dtype=np.int32)

    def served():
        """A prefill of every row from 0, then `n_dec` decode steps with
        the rows at their own lengths: [(logits, cache)] a call."""
        step = jax.jit(lambda p, t, c, s: forward(p, t, cfg, c, s))
        out = [step(params, jnp.asarray(tokens[:, :n_pre]),
                    init_cache(cfg, rows, 32), jnp.zeros(rows, jnp.int32))]
        at = np.arange(rows)
        for i in range(n_dec):
            out.append(step(
                params, jnp.asarray(tokens[at, lens + i][:, None]),
                out[-1][1], jnp.asarray(lens + i, jnp.int32)))
        return jax.tree.map(np.asarray, out)

    now = served()
    monkeypatch.setattr(decoder, "layers", scanned_layers)
    monkeypatch.setattr(module, name, sliced)
    before = served()
    assert jax.tree.structure(now) == jax.tree.structure(before)
    for call, (got, want) in enumerate(zip(now, before)):
        for x, y in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert x.dtype == y.dtype and np.array_equal(x, y), call
    # The decode steps wrote: a row's last key is not the prefill's.
    assert not np.array_equal(jax.tree.leaves(now[-1][1])[0],
                              jax.tree.leaves(now[0][1])[0])


# ---------------------------------------------------------------------------
# A decode step through the kernel
# ---------------------------------------------------------------------------


def through_the_kernel(monkeypatch, module, rows):
    """What `module`'s mixer sees on a TPU, on the CPU: a call of one
    token a slot goes to `decode_attention`, interpreted, in blocks of
    at most `rows` positions."""
    monkeypatch.setattr(attention, "_DECODE_ROWS", rows)
    monkeypatch.setattr(module, "attention", types.SimpleNamespace(
        on_tpu=lambda: True, decode_attention=functools.partial(
            attention.decode_attention, interpret=True)))


@pytest.mark.parametrize("rows", [16, 256], ids=["blocks-of-16", "one-block"])
@pytest.mark.parametrize("lens", [(17, 9, 1), (16, 32, 33), (64, 5, 48)],
                         ids=lambda lens: "-".join(map(str, lens)))
def test_a_decode_step_through_the_kernel_equals_the_plain_path(
        monkeypatch, lens, rows):
    """Rows prefilled to their own lengths decode four steps together,
    once as the CPU runs them and once as a TPU does: the same logits
    (float32, to a few of its last digits: the kernel sums a block at a
    time) and the same rows written."""
    cfg = LlamaConfig.debug()
    params = init_params(cfg, jax.random.PRNGKey(3))
    lens, steps = np.asarray(lens), 4
    tokens = np.random.default_rng(int(lens.sum())).integers(
        0, cfg.vocab_size, (len(lens), lens.max() + steps), dtype=np.int32)
    _, filled = forward_with_cache(
        params, jnp.asarray(tokens[:, :lens.max()]), cfg,
        init_kv_cache(cfg, len(lens), 96), jnp.zeros(len(lens), jnp.int32))

    def decoded():
        out, cache, at = [], filled, np.arange(len(lens))
        for i in range(steps):
            logits, cache = forward_with_cache(
                params, jnp.asarray(tokens[at, lens + i][:, None]), cfg,
                cache, jnp.asarray(lens + i, jnp.int32))
            out.append(np.asarray(logits))
        return np.stack(out), cache

    want, plain_cache = decoded()
    through_the_kernel(monkeypatch, llama, rows)
    got, cache = decoded()
    np.testing.assert_allclose(got, want, atol=2e-6 * np.abs(want).max())
    assert not np.array_equal(got, want)  # it did go another way
    for x, y in zip(jax.tree.leaves(cache), jax.tree.leaves(plain_cache)):
        np.testing.assert_allclose(x, y, atol=1e-6)


@pytest.mark.parametrize("family,cfg,read", [
    # Mistral-7B's key heads: blocks of 256 rows.
    ("dense", dataclasses.replace(LlamaConfig(), n_kv_heads=8, dim=4096,
                                  n_heads=32, dtype=jnp.bfloat16),
     [256, 256, 512, 1024]),
    # A model whose step reads no region by its length names none.
    ("glm_dsa", WALKED["glm_dsa"][0], None),
], ids=["dense", "glm_dsa"])
def test_the_rows_a_step_reads_are_the_lengths_in_whole_blocks(family, cfg,
                                                               read):
    from ray_tpu.models.serving import served_model
    keys_read = served_model(cfg).keys_read
    if read is None:
        assert keys_read is None
    else:
        got = keys_read(cfg, np.array([1, 256, 257, 1000]))
        assert got.tolist() == read
