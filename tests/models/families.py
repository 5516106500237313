"""The served families as the tests know them: one row each, the only
place a family is spelled for the tests. A row names the program's
module, the cell's config file and what its debug config changes, what
`test_serving.py` holds of the seam (state leaves, optional functions,
the seeded weights' digest) and the family's own expected values of the
tests every family shares (`test_served_contract.py`). The adapter and
the reference come from the config file, as `tools/glm_logit_check.py`
`main` finds them; the faults, the unseen faults and the rehearsal
lengths are read from that tool's `FAMILIES` by the file's `family`.

Below the table, what those tests share, each built when a test asks
for it and kept for the worker's life: the configs, the parameters, one
`jax.jit` of the program's two forward passes a family, the check's
weights with the reference's logits, and the reference's greedy answer
at one shape. No test lives here."""

import dataclasses
import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness.manifest import ROOT, load_json, model_adapter, plugin
from ray_tpu.models import llama, serving
from tools import glm_logit_check

# The seed of the check's weights and tokens in every family's tests.
CHECK_SEED = 2 ** 31 + 5
# Every engine test's prompt and answer together are at most this long:
# the reference decodes them padded to it, one shape a family.
ANSWER_ROWS = 32


def _field(**default):
    return dataclasses.field(default_factory=lambda: dict(default))


@dataclasses.dataclass(frozen=True)
class Family:
    module: str
    # The cell's file under benchmark/configs, what the debug config
    # changes of it, and what of its `serve` plan (the check's rows).
    file: str = ""
    over: dict = _field()
    check: dict = _field()
    # The cache leaves that are state, by name.
    state: frozenset = frozenset()
    # The optional functions the family gives (the rest are defaults).
    given: frozenset = frozenset()
    # sha256 over every leaf of init_params(cfg, PRNGKey(0)), recorded
    # at the commit before the stack was shared (PR 45), or at the PR
    # that brought the family (PR 55, PR 57): the seeded weights are
    # part of what a cell measures.
    weights: str = ""
    # The stack the forward tests run and the one the engine serves:
    # `with_layers` of the debug config, 0 the debug config whole.
    layers: int = 0
    engine_layers: int = 0
    key: int = 2
    rows: int = 32              # the forward tests' cache
    # The check: the program's largest error stays under `program`, a
    # fault's goes over `fault` (`faults` for those held to another).
    program: float = 0.0
    fault: float = 0.0
    faults: dict = _field()
    tool_faults: str = ""       # the tool's function, by its name
    named: frozenset = frozenset()  # faults the tool must have
    n_faults: int = 0
    # The experts one of the deployment's shares holds, of the debug
    # widths' 16.
    share: int = 0
    # `cache_state`: `state_leaves` of the forward tests' cache, leaf by
    # leaf; `engine_state` of the engine's. `cache_leaves`: shapes by
    # (run, name) in a cache of two rows of `contract_rows`.
    cache_state: tuple = ()
    engine_state: tuple = ()
    contract_rows: int = 16
    cache_leaves: dict = _field()
    # The counts a forward pass reports: their names (all of them where
    # `counts_exact`), and their values after the contract's call of
    # 2 x 6 tokens, a padded prefill, a prefill's second call and the
    # decode step after rows of different lengths.
    counts: frozenset = frozenset()
    counts_exact: bool = False
    contract_counts: dict = _field()
    padded_counts: dict = _field()
    second_counts: dict = _field()
    decode_counts: dict = _field()
    param_names: frozenset = frozenset()
    keys_attended: tuple = ()   # (context lengths, keys attended)
    # A padded prefill: real tokens, bucket, tolerance of the logits;
    # whether every state leaf absorbs the padding without `at` (and
    # moves under a request), or the state as a whole.
    padded: tuple = (13, 16, 1e-6)
    every_leaf: bool = True
    # A prefill in two calls: tokens, the first call's, tolerance.
    two_calls: tuple = ()
    long_row: int = 17          # of the rows of different lengths
    # Scopes a lowered step has and lacks: decode, prefill.
    scopes: dict = _field()
    # The engine: bytes of a block's token, the totals a run leaves, what
    # the decode spans carry (`consumed_with` picks the model's own).
    block_token_bytes: int = 0
    totals: object = None
    consumed_with: str = ""
    consumed: object = None
    dispatched: tuple = ()      # (which dispatches, what they hold)
    spans_prompt: int = 13
    warms_up: bool = False
    retired: tuple = (11, 7)    # the second request: prompt, max_tokens
    beside: tuple = (23, 9, 14)


_EXPERT_COUNTS = ("experts_held_steps", "experts_touched", "pair_overflows",
                  "pairs_held", "pairs_routed")
_DELTA_SCOPES = {
    "decode": ("delta/delta_conv", "delta/delta_update", "delta/delta_norm",
               "attn/", "mlp/"),
    "prefill": ("delta/delta_scan",),
    "not decode": ("delta/delta_scan", "attn/delta", "delta/attn"),
    "not prefill": ("delta/delta_update", "attn/delta", "delta/attn")}
_LFM2_SCOPES = ("conv/conv_in", "attn/", "mlp/router", "mlp/moe_dispatch",
                "/expert_matmul")

ROWS = {
    "LlamaConfig": Family("llama", given=frozenset({"keys_read"})),
    "GlmDsaConfig": Family(
        "glm_dsa", "glm-5.2-serve.json", over={"index_topk": 8},
        check={"reference_prompt_lens": [72, 60, 48, 40],
               "reference_decode_steps": 4},
        given=frozenset({"keys_attended"}),
        weights="c17b38efae68d04009a29f060d87a42d"
                "d79946c232cbadb11e6edb4f62f818e8",
        # (3e-5 > 100 x the program's error: the faults' limit.)
        program=3e-7, fault=3e-5, tool_faults="faults", n_faults=9,
        share=4,
        consumed_with="pairs_routed",
        consumed=lambda a: a["pairs_held"] <= a["pairs_routed"],
        dispatched=(lambda a: "keys_cached" in a,
                    lambda a, cfg: a["keys_attended"]
                    == min(a["keys_cached"], cfg.index_topk)),
        totals=lambda t: t["pairs_routed"] > 0 == t["pair_overflows"]),
    "NemotronHConfig": Family(
        "nemotron_h", "nemotron-3-super-serve.json",
        # 45 is no multiple of the 8-token chunk and no bucket: the
        # check pads it to 64; the shorter rows decode from their own
        # lengths.
        check={"reference_prompt_lens": [45, 39, 26, 19],
               "reference_decode_steps": 8},
        state=frozenset({"ssm", "conv"}),
        given=frozenset({"state_leaves"}),
        weights="76a4c01e9e63a2e718c9d9e152e98a80"
                "14c04c5c2223fa0da231480db0ee5a30",
        # (1e-5 > 20 x the program's error.)
        program=5e-7, fault=1e-5, tool_faults="nemotron_faults", n_faults=11,
        share=4,
        cache_state=(True,) * 4 + (False,) * 2,
        engine_state=(True,) * 4 + (False,) * 2,
        counts=frozenset(_EXPERT_COUNTS), counts_exact=True,
        # Three expert layers of four held experts each.
        contract_counts={"experts_held_steps": 12,
                         "pairs_routed": 3 * 2 * 6 * 3},
        every_leaf=False, two_calls=(21, 11, 1e-6),
        scopes={"decode": ("ssm/ssm_conv", "ssm/ssm_update",
                           "mlp/latent_down", "mlp/latent_up",
                           "mlp/shared_expert", "mlp/router", "attn/"),
                "prefill": ("ssm/ssm_scan",),
                "not decode": ("ssm/ssm_scan", "attn/ssm", "ssm/attn"),
                "not prefill": ("ssm/ssm_update", "attn/ssm", "ssm/attn")},
        # Blocks and read-backs are sized by the rows alone: one
        # attention layer's keys and values.
        block_token_bytes=2 * 2 * 16 * 4,
        totals=lambda t: 0 < t["pairs_held"] < t["pairs_routed"]
        and 0 < t["experts_touched"] <= t["experts_held_steps"]
        and t["experts_held_steps"] % 12 == 0,
        consumed_with="experts_touched",
        consumed=lambda a: 0 < a["experts_touched"]
        <= a["experts_held_steps"] == 12
        and a["pairs_held"] <= a["pairs_routed"],
        warms_up=True),
    "Cohere2MoeConfig": Family(
        "cohere2_moe", "command-a-plus-serve.json",
        # 45 is no bucket: the check pads it to 64, eight times the
        # ring; the shorter rows decode from their own lengths.
        check={"reference_prompt_lens": [45, 39, 26, 19],
               "reference_decode_steps": 8},
        state=frozenset({"ring_k", "ring_v"}),
        given=frozenset({"state_leaves", "keys_attended"}),
        weights="3d83ff31e8390e28a1c1d0b33f41eb8c"
                "5d6513e7e791234821ad53599a83bc41",
        key=0,
        # Every compared logit error is under 1e-5, every fault's
        # largest over it: the program by a factor of 50 and more, the
        # faults ISSUE 39 names for the chip's check and the ring's own
        # by 50 (the smallest, rotary positions on the full layer,
        # 5.8e-4), the others by 2.
        program=1e-5 / 50, fault=2 * 1e-5,
        faults=dict.fromkeys((
            "lower precision", "window ignored", "rope on the full layer",
            "shared experts summed", "sequential block",
            "pad enters the ring"), 50 * 1e-5),
        tool_faults="cohere_faults", n_faults=8, share=2,
        cache_state=(True, True, False, False),
        engine_state=(True, True, False, False),
        contract_rows=32,
        cache_leaves={(0, "ring_k"): (3, 2, 8, 2, 16),
                      (0, "ring_v"): (3, 2, 8, 2, 16),
                      (1, "k"): (1, 2, 32, 2, 16),
                      (1, "v"): (1, 2, 32, 2, 16)},
        counts=frozenset(_EXPERT_COUNTS), counts_exact=True,
        contract_counts={"experts_held_steps": 16,
                         "pairs_routed": 4 * 2 * 6 * 3},
        # Of the keys four full layers would read, three windows and one
        # whole context: (3 min(L, 8) + L) / 4.
        keys_attended=((3, 8, 20, 100),
                       (3, 8, (24 + 20) // 4, (24 + 100) // 4)),
        scopes={"decode": ("attn/window", "mlp/shared_expert", "mlp/router"),
                "prefill": ("attn/window", "mlp/shared_expert",
                            "mlp/router"),
                "not decode": ("mlp/window",), "not prefill": ("mlp/window",)},
        # Blocks would be sized by the rows alone: the full layer's.
        block_token_bytes=2 * 2 * 16 * 4,
        totals=lambda t: 0 < t["pairs_held"] < t["pairs_routed"]
        and 0 < t["keys_attended"] < t["keys_cached"],
        consumed_with="experts_held_steps",
        consumed=lambda a: a["experts_held_steps"] == 16
        and 0 < a["experts_touched"] <= 16,
        dispatched=(lambda a: a.get("keys_cached", 0) >= 29,
                    lambda a, cfg: a["keys_attended"]
                    == (3 * 8 + a["keys_cached"]) // 4),
        spans_prompt=29, warms_up=True,
        # A prompt shorter than the ring gets the slot a longer request
        # left, whose rings are full of that request's keys.
        retired=(5, 9), beside=(23, 6, 14)),
    "OlmoHybridConfig": Family(
        "olmo_hybrid", "olmo-hybrid-7b-serve.json",
        check={"reference_prompt_lens": [45, 39, 26, 19],
               "reference_decode_steps": 8},
        state=frozenset({"state", "conv_q", "conv_k", "conv_v"}),
        given=frozenset({"state_leaves", "keys_read"}),
        weights="db71416ce808872b3f6340bae503fb5e"
                "bdf2d8821b44ae01cc49259e97929de0",
        # One period is enough for the engine: three delta layers and
        # the full one.
        engine_layers=4,
        # Every fault reads at least 100 times the program's error.
        program=2e-6, fault=2e-4, tool_faults="olmo_faults", n_faults=10,
        named=frozenset({
            "lower precision", "beta without its 2", "gate before the norm",
            "k not normalised", "q without its scale", "no decay",
            "pad absorbed", "no q and k norm", "norm on the input"}),
        cache_state=((True,) * 4 + (False,) * 2) * 2,
        engine_state=(True,) * 4 + (False,) * 2,
        cache_leaves={(0, "state"): (3, 2, 3, 8, 16),
                      (0, "conv_q"): (3, 2, 3, 24),
                      (0, "conv_k"): (3, 2, 3, 24),
                      (0, "conv_v"): (3, 2, 3, 48),
                      (1, "k"): (1, 2, 16, 3 * 20)},
        counts=frozenset({"delta_scan_tokens", "delta_state_resets"}),
        counts_exact=True,
        two_calls=(21, 11, 1e-5),
        padded_counts={"delta_scan_tokens": 2 * 13, "delta_state_resets": 2},
        second_counts={"delta_scan_tokens": 2 * 10, "delta_state_resets": 0},
        decode_counts={"delta_scan_tokens": 0, "delta_state_resets": 0},
        scopes=_DELTA_SCOPES,
        # A decode step scans nothing; the engine drops a prefill's
        # counts.
        totals=lambda t: t["delta_scan_tokens"] == 0,
        consumed_with="delta_state_resets",
        # The slot that never held a request stands at position 0 and
        # starts from zeros at every step.
        consumed=lambda a: a["delta_scan_tokens"] == 0
        and 0 <= a["delta_state_resets"] <= 2),
    "SdarMoeConfig": Family(
        "sdar_moe", "sdar-30b-a3b-serve.json",
        # 44 is whole blocks and no bucket: the check pads it to 64; the
        # shorter rows step from their own lengths.
        check={"reference_prompt_lens": [44, 36, 24, 12],
               "reference_block_steps": 2},
        given=frozenset({"keys_read"}),
        weights="1b22ab037784016de8ad761330f20cd7"
                "3625010e91da2878e71e44e3763e96a7",
        program=1e-5, fault=1e-3),
    "Lfm2MoeConfig": Family(
        "lfm2_moe", "lfm2-8b-a1b-serve.json",
        # 45 is no bucket: the check pads it to 64; the shorter rows
        # decode from their own lengths, the shortest from 5, where the
        # first two positions (what a carry that was not zeroed moves)
        # still weigh.
        check={"reference_prompt_lens": [45, 33, 12, 5],
               "reference_decode_steps": 8},
        state=frozenset({"conv"}),
        given=frozenset({"state_leaves", "keys_read"}),
        weights="095f16a464f413890cf1264bf270d315"
                "73db4533b44ad90f5d1e0c492de252ad",
        # Published layers 1 to 4 are enough for the engine: a conv
        # layer with the dense FFN, a full layer and two conv layers
        # with experts.
        engine_layers=4,
        # Every fault reads at least ten times the limit the program
        # keeps (the weakest, a carry not zeroed, moves two positions of
        # a row's second prefill and reaches the compared decode steps
        # through the keys of those two alone).
        program=1e-6, fault=1e-5, tool_faults="lfm2_faults", n_faults=10,
        named=frozenset({
            "lower precision", "silu in the conv", "no B gate", "no C gate",
            "pad absorbed", "carry not zeroed", "no q and k norm",
            "bias in the gates", "gates not renormalised",
            "experts in the dense layers"}),
        cache_state=(True, False, False, True, False, False, True),
        engine_state=(True, False, False, True),
        cache_leaves={(0, "conv"): (2, 2, 2, 64), (1, "k"): (1, 2, 16, 2 * 8),
                      (2, "conv"): (3, 2, 2, 64), (4, "conv"): (1, 2, 2, 64)},
        counts=frozenset({"conv_prefill_tokens", "conv_state_resets",
                          "experts_touched", "experts_held_steps",
                          "pairs_held"}),
        two_calls=(21, 11, 1e-5),
        padded_counts={"conv_prefill_tokens": 2 * 13, "conv_state_resets": 2,
                       "pairs_routed": 6 * 2 * 16 * 3},  # six expert layers
        second_counts={"conv_prefill_tokens": 2 * 10, "conv_state_resets": 0},
        decode_counts={"conv_prefill_tokens": 0, "conv_state_resets": 0},
        # The head is the embedding: the tree has no `out`.
        param_names=frozenset({"embed", "runs", "final_norm"}),
        scopes={"decode": _LFM2_SCOPES, "prefill": _LFM2_SCOPES,
                "not decode": ("attn/conv", "conv/attn"),
                "not prefill": ("attn/conv", "conv/attn")},
        # A decode step carries no prefill; the engine drops a prefill's
        # counts.
        totals=lambda t: t["conv_prefill_tokens"] == 0
        and t["experts_touched"] > 0,
        consumed_with="conv_state_resets",
        consumed=lambda a: a["conv_prefill_tokens"] == 0
        and 0 <= a["conv_state_resets"] <= 2
        and a["experts_held_steps"] == 3 * 8),
    "KimiLinearConfig": Family(
        "kimi_linear", "kimi-linear-48b-a3b-serve.json",
        # 45 is no multiple of the 32-token chunk or of its 16-row
        # sub-block and no bucket: the check pads it to 64, two chunks
        # of two sub-blocks; the shorter rows decode from their own
        # lengths.
        check={"reference_prompt_lens": [45, 39, 26, 19],
               "reference_decode_steps": 8},
        state=frozenset({"state", "conv_q", "conv_k", "conv_v"}),
        given=frozenset({"state_leaves"}),
        weights="1bf01fb107f049aadc1a280ab34db915"
                "c388bad0375f0f738064ebc8b3d35eaf",
        # A layer of each kind, the three the check keeps: KDA over the
        # dense FFN, KDA over experts, latent attention over experts.
        layers=3, engine_layers=3, rows=64,
        # Read on the CPU in float32: the program 4e-7, the quietest
        # fault (the state rounded to bfloat16 after every call) 3e-4.
        program=2e-6, fault=1e-4, tool_faults="kimi_faults", n_faults=22,
        share=2,
        named=frozenset({
            "lower precision", "one decay a head",
            "decay after the correction", "beta doubled", "silu gate",
            "gate before the norm", "no dt bias", "k not normalised",
            "q without its scale", "conv without silu", "pad absorbed",
            "state not zeroed", "rotary turn", "score scaled by nope alone",
            "no latent norm", "no shared key channels", "no gate scale",
            "gates not renormalised", "bias in the gates",
            "no shared expert", "experts in the dense layer"}),
        cache_state=(True,) * 4 * 2 + (False,) * 2,
        engine_state=(True,) * 4 * 2 + (False,) * 2,
        cache_leaves={(1, "state"): (1, 2, 3, 8, 16),
                      (1, "conv_q"): (1, 2, 3, 24),
                      (1, "conv_k"): (1, 2, 3, 24),
                      (1, "conv_v"): (1, 2, 3, 48),
                      (2, "latent"): (1, 2, 16, 32),
                      (2, "rope"): (1, 2, 16, 128)},
        counts=frozenset({"delta_scan_tokens", "delta_state_resets",
                          "latent_keys_read", "pairs_held", "pairs_routed",
                          "experts_touched", "experts_held_steps"}),
        # 45 tokens in a bucket of 64, neither a multiple of the
        # 32-token chunk.
        padded=(45, 64, 2e-6), two_calls=(41, 23, 1e-5), long_row=37,
        padded_counts={"delta_scan_tokens": 2 * 45, "delta_state_resets": 2,
                       "latent_keys_read": 0},
        # One latent layer, two rows, the one block of 64 keys.
        decode_counts={"latent_keys_read": 1 * 2 * 64,
                       "delta_scan_tokens": 0},
        # The two rank-`gate_rank` projections lie under `delta_gate`
        # inside `delta`.
        scopes={"decode": ("delta/delta_conv", "delta/delta_gate",
                           "delta/delta_update", "delta/delta_norm",
                           "attn/mla_proj", "attn/latent_attn", "mlp/"),
                "prefill": ("delta/delta_scan", "attn/latent_attn"),
                "not decode": ("delta/delta_scan", "attn/delta",
                               "delta/attn", "delta/latent_attn"),
                "not prefill": ("delta/delta_update", "attn/delta",
                                "delta/attn", "delta/latent_attn")},
        totals=lambda t: t["delta_scan_tokens"] == 0
        and t["latent_keys_read"] > 0,
        consumed_with="latent_keys_read",
        consumed=lambda a: a["delta_scan_tokens"] == 0
        and 0 <= a["delta_state_resets"] <= 2
        and a["latent_keys_read"] % 64 == 0
        and a["pairs_routed"] >= a["pairs_held"]),
}
SERVED = sorted(serving._SERVED)
FAMILIES = [name for name in SERVED if name != "LlamaConfig"]


def having(field):
    """The families whose row has `field`: a test some families lack
    runs for those that have it, no wider."""
    return [name for name in FAMILIES if getattr(ROWS[name], field)]


# -- what a family's tests share ----------------------------------------------


def module(name):
    return importlib.import_module(f"ray_tpu.models.{ROWS[name].module}")


@functools.cache
def file(name):
    return load_json(ROOT, "benchmark", "configs", ROWS[name].file)


def adapter(name):
    return model_adapter(file(name))


def reference(name):
    return plugin("references", file(name)["reference"])


@functools.cache
def config(name):
    """The file at the adapter's debug widths with the row's changes,
    the check in a cache of 128 rows."""
    row = ROWS[name]
    debug = adapter(name).debug(file(name))
    return {**debug, **row.over,
            "serve": {**debug["serve"], "max_seq_len": 128, **row.check}}


@functools.cache
def cfg(name, layers=0):
    """The program's config of the debug widths, the whole stack or
    `with_layers` of it."""
    if name == "LlamaConfig":
        return llama.LlamaConfig.debug()
    whole = adapter(name).program_config(config(name))
    return adapter(name).with_layers(whole, layers) if layers else whole


def stack(name):
    """The config the forward tests run."""
    return cfg(name, ROWS[name].layers)


def served_stack(name):
    """The config the engine serves."""
    return cfg(name, ROWS[name].engine_layers)


@functools.cache
def params(name, layers=0, key=None):
    """The seeded weights of `cfg(name, layers)`, by the row's key
    unless another is asked for."""
    return jax.jit(functools.partial(module(name).init_params,
                                     cfg(name, layers)))(
        jax.random.PRNGKey(ROWS[name].key if key is None else key))


@functools.cache
def forward_with_cache(name):
    """`(params, tokens, cfg, cache, start_pos, at=)` through one jit a
    family: a shape a test chose is compiled once a worker."""
    return jax.jit(module(name).forward_with_cache, static_argnums=2)


@functools.cache
def forward(name):
    return jax.jit(module(name).forward, static_argnums=2)


def tokens(name, shape, seed=1):
    return jnp.asarray(np.random.default_rng(seed).integers(
        1, cfg(name).vocab_size, shape, dtype=np.int32))


def state(name, cache):
    return [x for x, is_state in zip(
        jax.tree.leaves(cache),
        jax.tree.leaves(module(name).state_leaves(cache))) if is_state]


def cache(name, rows=2, max_seq=None):
    return module(name).init_cache(stack(name), rows,
                                   max_seq or ROWS[name].rows)


def prompt(name, n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(
        1, cfg(name).vocab_size, n)]


# -- the check: the served path and its faults against the reference ----------


def _patched_sdar(**names):
    """SDAR's `cached_forward` with some names of `sdar_moe` replaced
    while it is traced."""
    from ray_tpu.models import sdar_moe
    cached_forward = adapter("SdarMoeConfig").cached_forward

    def served(params, tokens, cfg, cache, start_pos):
        before = {name: getattr(sdar_moe, name) for name in names}
        for name, value in names.items():
            setattr(sdar_moe, name, value)
        try:
            return cached_forward(params, tokens, cfg, cache, start_pos)
        finally:
            for name, value in before.items():
                setattr(sdar_moe, name, value)
    return served


def sdar_faults():
    """{name: served} for SDAR, whose check is the block runner's and
    whose faults the tool does not know: a fault of the mask is written
    into the plain path (an `own_keys` that never takes the kernel), and
    has to bite on a TPU too."""
    cached_forward = adapter("SdarMoeConfig").cached_forward

    def plain(tiled, start_pos, flash, plain):
        return plain()

    def gates_as_they_are(params, tokens, cfg, cache, start_pos):
        return cached_forward(
            params, tokens, dataclasses.replace(cfg, norm_topk_prob=False),
            cache, start_pos)

    def shifted(params, tokens, cfg, cache, start_pos):
        logits, cache = cached_forward(params, tokens, cfg, cache, start_pos)
        return jnp.roll(logits, 1, axis=1), cache

    def commit_keeps_the_denoised_keys(params, tokens, cfg, cache, start_pos):
        """A block step that holds no mask token (a commit) leaves the
        cache as the denoising pass before it left it."""
        logits, new = cached_forward(params, tokens, cfg, cache, start_pos)
        if tokens.shape[1] > cfg.block_length:
            return logits, new
        commit = ~(tokens == cfg.mask_token_id).any()
        return logits, jax.tree.map(
            lambda old, new: jnp.where(commit, old, new), cache, new)

    def padding_seen(params, tokens, cfg, cache, start_pos):
        """A prefill whose rows see every row of the call, its bucket's
        padding with them."""
        if tokens.shape[1] <= cfg.block_length:
            return cached_forward(params, tokens, cfg, cache, start_pos)
        return _patched_sdar(
            own_keys=plain, block_ends=lambda positions, block:
            jnp.full_like(positions, 10 ** 6))(
                params, tokens, cfg, cache, start_pos)

    return {
        "a causal mask where block causal is due": _patched_sdar(
            own_keys=plain, block_ends=lambda positions, block: positions),
        "q/k norm over all heads": _patched_sdar(
            norm_each_head=lambda x, w, eps: llama.norm_all_heads(
                x, jnp.tile(w, x.shape[2]), eps)),
        "gates not renormalised": gates_as_they_are,
        "interleaved in place of split-half rotary": _patched_sdar(
            apply_rope=lambda x, cos, sin: serving.rotate_pairs(x, cos, sin)),
        "logits shifted by one": shifted,
        "a commit that keeps the denoising pass's keys":
            commit_keeps_the_denoised_keys,
        "a padded prefill whose padding is seen": padding_seen,
    }


@functools.cache
def faults(name):
    """{fault: served} of the family: the tool's, by the file's
    `family`; SDAR's own."""
    if name == "SdarMoeConfig":
        return sdar_faults()
    model = adapter(name)
    return glm_logit_check.FAMILIES[file(name)["family"]][0](
        model.cached_forward, model.init_cache)


@functools.cache
def _check(name):
    """The check's weights (the program's initialiser's: the routed
    experts at their own scale), its tokens and the reference's logits
    of them."""
    small, weights, lens, toks = glm_logit_check.weights_and_tokens(
        config(name), CHECK_SEED, adapter(name), module(name).init_params)
    return small, weights, lens, toks, glm_logit_check.reference_logits(
        config(name), weights, toks, reference(name))


@functools.cache
def errors(name, fault="program"):
    """The statistics of the positions' errors of the program or one of
    its faults (`glm_logit_check.distances`; of SDAR the largest alone,
    by the block runner's check (a)), computed when a test asks."""
    model = adapter(name)
    served = model.cached_forward if fault == "program" \
        else faults(name)[fault]
    if name == "SdarMoeConfig":
        from benchmark.runners import serve_blocks
        # (A stand-in is called while the check traces it, not under a
        # jit of its own: a patch has to hold while it is traced.)
        init, model.init = model.init, module(name).init_params
        try:
            return {"max": serve_blocks.check_against_reference(
                config(name), CHECK_SEED,
                served=None if fault == "program" else served)[0]}
        finally:
            model.init = init
    small, weights, lens, toks, want = _check(name)
    return glm_logit_check.distances(
        config(name), small, weights, lens, toks, model, reference(name),
        {fault: served}, want=want)[fault]


# -- the reference's greedy answer, at one shape -------------------------------


@functools.cache
def _sequence_logits(name):
    ref, hp = reference(name), reference(name).hyper(config(name))
    return jax.jit(lambda params, tokens: ref.sequence_logits(
        params, tokens, hp))


def reference_rows(name, params, tokens, rows=ANSWER_ROWS):
    """The reference's logits of `tokens`' positions, from a call padded
    to `rows` tokens: a causal reference gives a position the same
    logits whatever follows it (`test_served_contract.py` holds that for
    each family), and one length compiles once."""
    padded = jnp.asarray(list(tokens) + [0] * (rows - len(tokens)), jnp.int32)
    return _sequence_logits(name)(params, padded)[:len(tokens)]


def is_greedy(name, params, prompt, answer):
    """Whether `answer` is greedy decoding by the reference: each of
    its tokens the largest logit of the reference's full forward pass
    over what came before it."""
    logits = reference_rows(name, params, (prompt + answer)[:-1])
    return answer == [int(t) for t in logits[len(prompt) - 1:].argmax(-1)]
