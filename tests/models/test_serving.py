"""The seam `ray_tpu/models/serving.py` states, held for every entry of
its registry at the family's smallest test config: what the engine
leans on (a cache that keeps its structure through `forward`, state
leaves where the family says, the logits of position `at`), the seeded
weights a cell's numbers are read on, and which of the optional
functions each family hands the engine. In the tests a further family
costs a row in `tests/models/families.py` (which this file and
`test_served_contract.py` read), its entry in
`tools/glm_logit_check.py` `FAMILIES`, and a `test_<family>.py` only
for what no other family has."""

import ast
import hashlib
import importlib
import inspect
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import serving
from tests.models import families
from tests.models.families import FAMILIES, ROWS, SERVED


def test_every_served_config_has_a_row():
    assert set(ROWS) == set(serving._SERVED)
    for name in ROWS:
        assert type(families.cfg(name)).__name__ == name


@pytest.mark.parametrize("name", SERVED)
def test_what_the_engine_leans_on(name):
    row, cfg = ROWS[name], families.cfg(name)
    served = serving.served_model(cfg)
    params = families.params(name, key=1)
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
    whole = families.forward_with_cache(name)
    every = (whole(params, tokens, cfg, cache, start)
             if name == "LlamaConfig"
             else whole(params, tokens, cfg, cache, start, at=at))[0]
    assert logits.shape == (2, cfg.vocab_size) \
        and logits.dtype == jnp.float32
    np.testing.assert_allclose(logits, every[:, at], rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("name", FAMILIES)
def test_seeded_weights_are_the_recorded_ones(name):
    params = families.module(name).init_params(families.cfg(name),
                                               jax.random.PRNGKey(0))
    leaves = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        leaf = np.asarray(leaf)
        digest = hashlib.sha256(
            str(leaf.dtype).encode() + str(leaf.shape).encode())
        digest.update(np.ascontiguousarray(leaf).view(np.uint8).tobytes())
        leaves[jax.tree_util.keystr(path)] = digest.hexdigest()
    assert hashlib.sha256(json.dumps(leaves, sort_keys=True).encode()
                          ).hexdigest() == ROWS[name].weights


@pytest.mark.parametrize("name", SERVED)
def test_the_optional_functions_a_family_gives(name):
    served = serving.served_model(families.cfg(name))
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

    cfg = families.cfg(name)
    served = serving.served_model(cfg)
    params = families.params(name, key=1)
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
