"""The seam `ray_tpu/models/serving.py` states, held for every entry of
its registry at the family's smallest test config: what the engine
leans on (a cache that keeps its structure through `forward`, state
leaves where the family says, the logits of position `at`), the seeded
weights a cell's numbers are read on, and which of the optional
functions each family hands the engine. A further family adds a row to
`ROWS` and no file."""

import ast
import dataclasses
import hashlib
import importlib
import inspect
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness.manifest import ROOT, load_json, model_adapter
from ray_tpu.models import llama, serving


def _debug(name, **over):
    """The config the family's own tests build: the adapter's debug
    widths of the cell's file."""
    file = load_json(ROOT, "benchmark", "configs", name)
    adapter = model_adapter(file)
    return adapter.program_config({**adapter.debug(file), **over})


@dataclasses.dataclass(frozen=True)
class Row:
    module: str
    config: object
    # The cache leaves that are state, by name.
    state: frozenset = frozenset()
    # The optional functions the family gives (the rest are defaults).
    given: frozenset = frozenset()
    # sha256 over every leaf of init_params(cfg, PRNGKey(0)), recorded
    # at the commit before the stack was shared (PR 45): the seeded
    # weights are part of what a cell measures.
    weights: str = ""


ROWS = {
    "LlamaConfig": Row("llama", llama.LlamaConfig.debug,
                       given=frozenset({"keys_read"})),
    "GlmDsaConfig": Row(
        "glm_dsa", lambda: _debug("glm-5.2-serve.json", index_topk=8),
        given=frozenset({"keys_attended"}),
        weights="c17b38efae68d04009a29f060d87a42d"
                "d79946c232cbadb11e6edb4f62f818e8"),
    "NemotronHConfig": Row(
        "nemotron_h", lambda: _debug("nemotron-3-super-serve.json"),
        state=frozenset({"ssm", "conv"}),
        given=frozenset({"state_leaves"}),
        weights="76a4c01e9e63a2e718c9d9e152e98a80"
                "14c04c5c2223fa0da231480db0ee5a30"),
    "Cohere2MoeConfig": Row(
        "cohere2_moe", lambda: _debug("command-a-plus-serve.json"),
        state=frozenset({"ring_k", "ring_v"}),
        given=frozenset({"state_leaves", "keys_attended"}),
        weights="3d83ff31e8390e28a1c1d0b33f41eb8c"
                "5d6513e7e791234821ad53599a83bc41"),
    "OlmoHybridConfig": Row(
        "olmo_hybrid", lambda: _debug("olmo-hybrid-7b-serve.json"),
        state=frozenset({"state", "conv_q", "conv_k", "conv_v"}),
        given=frozenset({"state_leaves", "keys_read"}),
        weights="db71416ce808872b3f6340bae503fb5e"
                "bdf2d8821b44ae01cc49259e97929de0"),
    "SdarMoeConfig": Row(
        "sdar_moe", lambda: _debug("sdar-30b-a3b-serve.json"),
        given=frozenset({"keys_read"}),
        weights="1b22ab037784016de8ad761330f20cd7"
                "3625010e91da2878e71e44e3763e96a7"),
    # (Recorded at the PR that brought the family, PR 55.)
    "Lfm2MoeConfig": Row(
        "lfm2_moe", lambda: _debug("lfm2-8b-a1b-serve.json"),
        state=frozenset({"conv"}),
        given=frozenset({"state_leaves", "keys_read"}),
        weights="095f16a464f413890cf1264bf270d315"
                "73db4533b44ad90f5d1e0c492de252ad"),
    # (Recorded at the PR that brought the family, PR 57.)
    "KimiLinearConfig": Row(
        "kimi_linear", lambda: _debug("kimi-linear-48b-a3b-serve.json"),
        state=frozenset({"state", "conv_q", "conv_k", "conv_v"}),
        given=frozenset({"state_leaves"}),
        weights="1bf01fb107f049aadc1a280ab34db915"
                "c388bad0375f0f738064ebc8b3d35eaf"),
}
SERVED = sorted(serving._SERVED)
FAMILIES = [name for name in SERVED if name != "LlamaConfig"]


def _module(name):
    return importlib.import_module(f"ray_tpu.models.{ROWS[name].module}")


def test_every_served_config_has_a_row():
    assert set(ROWS) == set(serving._SERVED)
    for name, row in ROWS.items():
        assert type(row.config()).__name__ == name


@pytest.mark.parametrize("name", SERVED)
def test_what_the_engine_leans_on(name):
    row, module = ROWS[name], _module(name)
    cfg = row.config()
    served = serving.served_model(cfg)
    params = module.init_params(cfg, jax.random.PRNGKey(1))
    cache = served.init_cache(cfg, 2, 32)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        1, cfg.vocab_size, (2, 8)), jnp.int32)
    start, at = jnp.zeros(2, jnp.int32), 5
    logits, new, counts = jax.jit(
        lambda p, c: served.forward(p, tokens, cfg, c, start, at))(
            params, cache)
    # A donated cache is updated where it lies only if the program
    # returns what it took: structure, leaf order, shapes and dtypes.
    assert jax.tree.structure(new) == jax.tree.structure(cache)
    assert [(x.shape, x.dtype) for x in jax.tree.leaves(new)] \
        == [(x.shape, x.dtype) for x in jax.tree.leaves(cache)]
    assert all(x.dtype == jnp.int32 and x.shape == ()
               for x in jax.tree.leaves(counts))
    flags = served.state_leaves(cache)
    assert jax.tree.structure(flags) == jax.tree.structure(cache)
    assert {path[-1].key for path, flag
            in jax.tree_util.tree_flatten_with_path(flags)[0] if flag} \
        == row.state
    every = (module.forward_with_cache(params, tokens, cfg, cache, start)
             if name == "LlamaConfig" else module.forward_with_cache(
                 params, tokens, cfg, cache, start, at=at))[0]
    assert logits.shape == (2, cfg.vocab_size) \
        and logits.dtype == jnp.float32
    np.testing.assert_allclose(logits, every[:, at], rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("name", FAMILIES)
def test_seeded_weights_are_the_recorded_ones(name):
    row = ROWS[name]
    params = _module(name).init_params(row.config(), jax.random.PRNGKey(0))
    leaves = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        leaf = np.asarray(leaf)
        digest = hashlib.sha256(
            str(leaf.dtype).encode() + str(leaf.shape).encode())
        digest.update(np.ascontiguousarray(leaf).view(np.uint8).tobytes())
        leaves[jax.tree_util.keystr(path)] = digest.hexdigest()
    assert hashlib.sha256(json.dumps(leaves, sort_keys=True).encode()
                          ).hexdigest() == row.weights


@pytest.mark.parametrize("name", SERVED)
def test_the_optional_functions_a_family_gives(name):
    served = serving.served_model(ROWS[name].config())
    defaults = serving.ServedModel(None, None)
    assert {field for field in ("state_leaves", "keys_attended", "keys_read")
            if getattr(served, field) is not getattr(defaults, field)} \
        == ROWS[name].given


def test_no_served_module_imports_a_siblings_private_name():
    for module in ("glm_dsa", "nemotron_h", "cohere2_moe", "olmo_hybrid",
                   "sdar_moe", "lfm2_moe", "kimi_linear", "gated_delta", "kda",
                   "mamba2"):
        tree = ast.parse(inspect.getsource(
            importlib.import_module(f"ray_tpu.models.{module}")))
        reached = [(node.module, alias.name) for node in ast.walk(tree)
                   if isinstance(node, ast.ImportFrom)
                   and (node.module or "").startswith("ray_tpu.models")
                   for alias in node.names if alias.name.startswith("_")]
        assert not reached, (module, reached)


@pytest.mark.parametrize("name", [n for n in SERVED if n != "SdarMoeConfig"])
def test_a_decode_step_by_the_kernel_is_the_step_by_the_scatter(
        name, monkeypatch):
    """A step of one token a slot writes its rows through
    `block_rows.write_tokens`, on a TPU the tile-rewrite kernel: run
    through the Pallas interpreter it leaves the cache and the logits
    the scatter leaves, to the bit, every leaf of every run, a fresh
    slot (start 0), a slot at a tile's last row and a retired one (the
    engine's clamp, max_seq - 2) among the rows."""
    from ray_tpu.ops import block_rows

    row, module = ROWS[name], _module(name)
    cfg = row.config()
    served = serving.served_model(cfg)
    params = module.init_params(cfg, jax.random.PRNGKey(1))
    slots, rows = 4, 32
    rng = np.random.default_rng(4)
    prompt = jnp.asarray(rng.integers(1, cfg.vocab_size, (slots, 16)),
                         jnp.int32)
    _, cache, _ = jax.jit(lambda p, c: served.forward(
        p, prompt, cfg, c, jnp.zeros(slots, jnp.int32), 15))(
            params, served.init_cache(cfg, slots, rows))
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, (slots, 1)),
                         jnp.int32)
    start = jnp.asarray([0, 9, 15, rows - 2], jnp.int32)

    def step():
        return jax.jit(lambda p, c: served.forward(
            p, tokens, cfg, c, start, 0)[:2])(params, cache)

    want = step()
    plain, seen = block_rows.write_tokens, []

    def through_the_interpreter(stacks, layer, new, start_pos):
        seen.append(block_rows._fits(tuple(stacks), new))
        return plain(stacks, layer, new, start_pos, interpret=True)

    monkeypatch.setattr(block_rows, "write_tokens", through_the_interpreter)
    got = step()
    # Every run of layers that keeps keys went through the kernel.
    assert seen and all(seen)
    for x, y in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))
