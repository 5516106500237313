"""SmallThinker's decoder (PowerInfer's SmallThinker-21BA3B-Instruct,
arXiv:2507.20984), trained: `decoder`'s sequential block over two kinds
of layer, one `full` to three `window` in the published model
(`benchmark/references/smallthinker.py` has the equations in full):

- a `full` layer (0 in the published `sliding_window_layout` and
  `rope_layout`) attends causally over every key and has no positional
  encoding at all;
- a `window` layer (1 in both) turns q and k by rotary positions (the
  halves of a head paired, as Llama's) and a row sees
  `sliding_window` keys, itself the last: `llama.self_attention` with a
  window, whose flash kernels skip the tiles outside it forward and
  backward, in the scope `window`;
- both are grouped-query attention with the head size a key of its own
  (28 heads of 128 over a stream of 2560), and behind both stands
  `moe`'s expert layer with three departures: the router scores the
  stream as the layer *received* it, before attention and before any
  norm (`decoder.block` hands it to an FFN that carries `stream`), in
  float32; the experts are ReGLU (`expert_kind` "reglu"); and the
  program may hold a share of them (`experts_held`), which is trained
  as it is: `moe._held_experts_trained`.

The stack is `decoder.hidden_runs` over runs of like layers, and the
parameters are {"embed", "runs": [a dict of stacked leaves a run],
"final_norm", "out"}, as the served families of several kinds of layer
keep theirs. The loss is `decoder.loss` over those runs (the output
projection fused into the cross-entropy) plus `aux_loss_coeff` times
the mean of the layers' load-balancing losses over all `n_experts`.

Not here: a cached forward pass (the family is trained, not served; the
ring of `sliding_window` rows and the held share's served kernel that a
`Family` declaration would stand on are `cohere2_moe`'s and `moe`'s),
the "secondary experts" of the family's paper, which this model's
`config.json` has no key for, and the exchange of tokens between the
chips that share a layer.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import decoder, llama, moe
from ray_tpu.parallel.sharding import DEFAULT_RULES

PUBLISHED_LAYER_KINDS = ("full", "window", "window", "window") * 13


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig(moe.MoEConfig):
    """Defaults are SmallThinker-21BA3B-Instruct's. `hidden_dim` is one
    expert's width; `layer_kinds` names the layers held, bottom to top,
    "full" or "window", `n_layers` of them."""
    vocab_size: int = 151936
    dim: int = 2560
    n_layers: int = 52
    n_heads: int = 28
    n_kv_heads: int = 4
    head_size: int = 128
    hidden_dim: int = 768
    max_seq_len: int = 16384
    rope_theta: float = 1.5e6
    norm_eps: float = 1e-6
    n_experts: int = 64
    n_experts_per_token: int = 6
    norm_topk_prob: bool = True
    expert_kind: str = "reglu"
    layer_kinds: Tuple[str, ...] = PUBLISHED_LAYER_KINDS
    # A row of a `window` layer sees this many keys, itself among them.
    sliding_window: int = 4096

    @property
    def head_dim(self) -> int:
        """Published beside the hidden size, not its quotient by the
        heads: 28 heads of 128 over a stream of 2560."""
        return self.head_size

    def runs(self):
        """[(kind, layers)]: the stack as runs of like layers."""
        assert len(self.layer_kinds) == self.n_layers \
            and set(self.layer_kinds) <= {"full", "window"}, self.layer_kinds
        return [(kind, len(list(group)))
                for kind, group in itertools.groupby(self.layer_kinds)]

    def num_params(self) -> int:
        """From the shapes `init_params` draws."""
        d, hd = self.dim, self.head_dim
        layer = 2 * d + 2 * d * self.n_heads * hd \
            + 2 * d * self.n_kv_heads * hd + d * self.n_experts \
            + self.n_experts_held * 3 * d * self.hidden_dim
        return self.n_layers * layer + 2 * self.vocab_size * d + d

    @staticmethod
    def debug() -> "SmallThinkerConfig":
        return SmallThinkerConfig(
            vocab_size=512, dim=64, n_layers=4, n_heads=4, n_kv_heads=2,
            head_size=32, hidden_dim=32, max_seq_len=128, n_experts=8,
            n_experts_per_token=3, sliding_window=16,
            layer_kinds=PUBLISHED_LAYER_KINDS[:4], dtype=jnp.float32,
            remat=False)


def _init_layer(cfg: SmallThinkerConfig, key) -> Dict[str, Any]:
    return {**llama.attention_init(cfg, key),
            **moe.expert_init(cfg, jax.random.split(
                jax.random.fold_in(key, 99), 4))}


def init_params(cfg: SmallThinkerConfig, rng) -> Dict[str, Any]:
    k_embed, k_out, k_layers = jax.random.split(rng, 3)
    init = jax.nn.initializers.normal(0.02)
    keys = jax.random.split(k_layers, cfg.n_layers)
    runs, at = [], 0
    for _, n in cfg.runs():
        runs.append(jax.vmap(functools.partial(_init_layer, cfg))(
            keys[at:at + n]))
        at += n
    return {"embed": init(k_embed, (cfg.vocab_size, cfg.dim), cfg.dtype),
            "runs": runs, "final_norm": jnp.ones(cfg.dim, cfg.dtype),
            "out": init(k_out, (cfg.dim, cfg.vocab_size), cfg.dtype)}


def param_logical_axes(cfg: SmallThinkerConfig) -> Dict[str, Any]:
    layer = {**llama.attention_axes(cfg), "router": ("embed", None),
             **moe._EXPERT_AXES}
    axes = decoder.param_logical_axes(cfg, layer)
    axes["runs"] = [axes["layers"] for _ in cfg.runs()]
    del axes["layers"]
    return axes


def init_params_sharded(cfg: SmallThinkerConfig, mesh, rng,
                        rules=DEFAULT_RULES):
    return decoder.init_params_sharded(
        functools.partial(init_params, cfg), param_logical_axes(cfg), mesh,
        rng, rules)


# What an expert layer reports beside its load-balancing loss and its
# counts by expert: `moe._moe_ffn`'s int32 scalars, summed over layers.
_SHARE = ("pairs_held", "pairs_routed", "pair_overflows", "experts_touched")


def _ffn(cfg: SmallThinkerConfig, mesh, rules):
    """The expert layer: the experts read the normed stream behind
    attention, the router the stream the layer received, in float32."""
    def ffn(h, lp, stream):
        out, aux, counts, share = moe._moe_ffn(
            cfg, lp, h, mesh, rules, routed=stream.astype(jnp.float32),
            trained=True)
        return out, {"aux": aux, "counts": counts,
                     **{name: share[name] for name in _SHARE}}

    ffn.stream = True
    return ffn


def _runs(params, cfg: SmallThinkerConfig, mesh, rules):
    """What `decoder.hidden_runs` is handed: a run's mixer by its kind,
    the expert layer, the run's stacked parameters, no state."""
    mixers = {
        "full": llama.self_attention(cfg, mesh, rules, turned=False),
        "window": llama.self_attention(cfg, mesh, rules,
                                       window=cfg.sliding_window)}
    ffn = _ffn(cfg, mesh, rules)
    return [(mixers[kind], ffn, stacked, None)
            for (kind, _), stacked in zip(cfg.runs(), params["runs"])]


def _by_layer(extras):
    """A list of extras, a run each, stacked by layer -> one, [L, ...]."""
    return jax.tree.map(lambda *leaves: jnp.concatenate(leaves), *extras)


def forward(params, tokens, cfg: SmallThinkerConfig, *, mesh=None,
            rules=DEFAULT_RULES):
    """tokens [B, S] -> (logits [B, S, V], the layers' mean aux loss,
    pairs routed per layer and expert [L, E] int32)."""
    x, _, extras = decoder.hidden_runs(
        params, tokens, cfg, _runs(params, cfg, mesh, rules), mesh=mesh,
        rules=rules)
    extras = _by_layer(extras)
    return (decoder.logits(params, x, cfg, mesh=mesh, rules=rules),
            extras["aux"].mean(), extras["counts"])


def loss_fn(params, batch, cfg: SmallThinkerConfig, *, mesh=None,
            rules=DEFAULT_RULES):
    """Mean cross-entropy plus `aux_loss_coeff` times the mean of the
    layers' load-balancing losses (over all `n_experts`). The metrics
    carry the step's routing: `expert_tokens` [L, E], the pairs sent to
    each expert of each layer, and under `span_attrs` (what
    `make_train_step` puts on its dispatch span) the pairs the layers
    routed and the pairs they held and computed, the held experts a
    pair fell on, the buffers beyond a layer's first (all summed over
    layers), and the busiest held expert's count beside the held
    experts' mean."""
    # A rematerialised layer here keeps nothing but its input, where
    # `moe._SAVED` keeps the attention kernel's output and the grouped
    # products: at this family's 16k sequences `flash_out` alone is
    # 1.9 GB over four layers and a held share's hidden products 0.45 GB
    # a layer, and its cell stands at 90.3 % of a v5e's memory. The
    # dk/dv kernel of PERF.md section 7 would free what they need.
    ce, _, extras = decoder.loss(
        params, batch, cfg, runs=_runs(params, cfg, mesh, rules), mesh=mesh,
        rules=rules, save=[] if cfg.remat else None)
    extras = _by_layer(extras)
    aux = extras["aux"].mean()
    expert_tokens = extras["counts"]
    first, count = cfg.experts_held or (0, cfg.n_experts)
    held = expert_tokens[:, first:first + count]
    loss = ce + cfg.aux_loss_coeff * aux
    return loss, {"loss": loss, "ce_loss": ce, "aux_loss": aux,
                  "expert_tokens": expert_tokens,
                  "span_attrs": {
                      **{name: extras[name].sum() for name in _SHARE},
                      "expert_tokens_max": held.max(),
                      "expert_tokens_mean": held.sum() // held.size,
                      "expert_tokens_min": held.min()}}
