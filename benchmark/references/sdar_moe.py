"""Plain reference of SDAR's decoder (`model_type` `sdar_moe`,
SDAR-30B-A3B-Chat) and of its generation by diffusion over blocks,
written from the catalog's `config` beside the model-configs guide and
from the family's published generation loop. Sequential blocks,
u = RMSNorm(x) (a weight, eps `rms_norm_eps`), B = `block_length`:

    x' = x + Attn(u) ;  x'' = x' + MoE(RMSNorm(x'))
    Attn: q = u Wq [heads x 128], k = u Wk, v = u Wv [kv heads x 128]
          q, k: RMSNorm over the 128 channels of each head, one weight [128] for q's heads and one for k's
          q, k turned by rotary positions (theta, split halves: channel i with i + 64) at absolute positions
          out = softmax(q k^T / sqrt(128) + mask) v Wo      query head i reads kv head i // (heads / kv heads)
          mask: key j is seen from i iff j // B <= i // B    causal between blocks, both ways inside one
    MoE:  p = softmax(u' Wr) in R^experts ;  T = the num_experts_per_tok largest of p ;  g_e = p_e / sum_T p
          MoE(u') = sum_{e in T} g_e W2_e (silu(W1_e u') * W3_e u')        no shared expert
    logits = RMSNorm_f(x_L) Wout        untied; NO shift: the logits at position i score the token AT i

    generate(prompt): the prompt's whole blocks are the committed prefix; what is left over, len % B tokens,
      opens the first block as known positions. A block's unknown positions hold the mask token. A denoising
      step runs prefix + block through the model, takes at every open position x0 = argmax logits and its
      confidence softmax(logits)[x0], and fixes the B / denoising_steps open positions of highest confidence
      (those that are left, if fewer; ties to the lower position). When none is open the block joins the
      prefix with its final tokens (the cached model runs it once more for that, the commit) and the next
      block opens. A request ends at `max_tokens` or on a stop id, inside a block: the rest is dropped.

Float32 `jax.numpy`, no cache and no kernels: a whole sequence at once
under a dense mask, one sequence, head and expert after the other (a
matrix is cast to float32 when its turn comes, so that a layer's
float32 copy never stands whole beside a deployment). It reads the
program's parameter tree and nothing else of the program: `runs`, a
list of stacked runs of like layers. Call it under
`jax.default_matmul_precision("highest")`.

`noised_logits` is what both checks of a served answer stand on: one
pass over a clean sequence followed by any number of noised copies of
blocks of it, each copy at its block's own positions and seeing the
clean tokens before its block and itself: the logits of every copy as
if it alone had been run against that prefix (the mask of
block-diffusion training, used here to read many denoising steps in
one pass).

Departures from the published description: weights are random, from
the program's initialiser; temperature 0 only (greedy x0); of the two
published remasking schedules the static one (`low_confidence_static`);
the dynamic threshold is not written (random weights never pass a
confidence threshold, so it would degenerate to the static count).
What the catalog leaves open (block length, the schedule) is the
configuration's `assumed`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references.dense_decoder import _f32


def hyper(config):
    generation = config["generation"]
    return {
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "rope_theta": float(config["rope_theta"]),
        "norm_eps": float(config["rms_norm_eps"]),
        "top_k": config["num_experts_per_tok"],
        "norm_topk": bool(config["norm_topk_prob"]),
        "block_length": generation["block_length"],
        "denoising_steps": generation["denoising_steps"],
        "mask_token_id": generation["mask_token_id"],
    }


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def rotate_halves(x, positions, theta):
    """x [S, H, D] turned at `positions` [S]: channel i with i + D/2."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None, None] * freqs
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(angles) - b * jnp.sin(angles),
                            b * jnp.cos(angles) + a * jnp.sin(angles)], -1)


def block_causal(positions, block):
    """[S, S]: row i true at the keys j it sees."""
    return positions[None, :] // block <= positions[:, None] // block


def attention(u, lp, positions, seen, hp):
    """One layer's attention on normed activations u [S, d]."""
    q = jnp.einsum("sd,dhk->shk", u, _f32(lp["wq"]))
    k = jnp.einsum("sd,dhk->shk", u, _f32(lp["wk"]))
    v = jnp.einsum("sd,dhk->shk", u, _f32(lp["wv"]))
    q = rms_norm(q, _f32(lp["q_norm"]), hp["norm_eps"])
    k = rms_norm(k, _f32(lp["k_norm"]), hp["norm_eps"])
    q = rotate_halves(q, positions, hp["rope_theta"])
    k = rotate_halves(k, positions, hp["rope_theta"])
    group = hp["n_heads"] // hp["n_kv_heads"]
    scale = q.shape[-1] ** -0.5

    def one_head(xs):
        q_h, head = xs                                         # [S, D]
        scores = q_h @ k[:, head // group].T * scale
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return probs @ v[:, head // group]

    out = jax.lax.map(one_head, (q.transpose(1, 0, 2),
                                 jnp.arange(hp["n_heads"])))  # [H, S, D]
    return jnp.einsum("hsk,hkd->sd", out, _f32(lp["wo"]))


def experts(u, run, i, hp):
    """Layer i's expert half on u [S, d]; the matrices are picked out
    of the run's stacks one at a time."""
    p = jax.nn.softmax(u @ _f32(run["router"][i]), -1)
    gates, chosen = jax.lax.top_k(p, hp["top_k"])
    if hp["norm_topk"]:
        gates = gates / gates.sum(-1, keepdims=True)

    def routed(out, e):
        weight = jnp.where(chosen == e, gates, 0.0).sum(-1)          # [S]
        hidden = jax.nn.silu(u @ _f32(run["we1"][i, e])) \
            * (u @ _f32(run["we3"][i, e]))
        return out + weight[:, None] * (hidden @ _f32(run["we2"][i, e])), None

    out, _ = jax.lax.scan(routed, jnp.zeros_like(u),
                          jnp.arange(run["we1"].shape[1]))
    return out


def block(x, run, i, positions, seen, hp):
    """Layer `i` of the stacked `run`. x: [S, d]."""
    lp = {name: run[name][i] for name in ("attn_norm", "mlp_norm", "q_norm",
                                          "k_norm", "wq", "wk", "wv", "wo")}
    x = x + attention(rms_norm(x, _f32(lp["attn_norm"]), hp["norm_eps"]),
                      lp, positions, seen, hp)
    return x + experts(rms_norm(x, _f32(lp["mlp_norm"]), hp["norm_eps"]),
                       run, i, hp)


def layers_of(params):
    for run in params["runs"]:
        for i in range(run["wq"].shape[0]):
            yield run, i


def head(params, x, hp):
    return rms_norm(x, _f32(params["final_norm"]), hp["norm_eps"]) \
        @ _f32(params["out"])


def sequence_logits(params, tokens, hp, positions=None, seen=None):
    """One sequence: tokens [S] -> logits [S, vocab], float32, at
    `positions` (0 .. S - 1 unless given) under the mask `seen` [S, S]
    (block causal over the positions unless given)."""
    if positions is None:
        positions = jnp.arange(tokens.shape[0])
    if seen is None:
        seen = block_causal(positions, hp["block_length"])
    x = _f32(params["embed"])[tokens]
    for run, i in layers_of(params):
        x = block(x, run, i, positions, seen, hp)
    return head(params, x, hp)


def forward(params, tokens, hp):
    """tokens [B, S] -> logits [B, S, vocab], one sequence at a time."""
    return jax.lax.map(lambda t: sequence_logits(params, t, hp), tokens)


def noised_inputs(n_clean, starts, hp):
    """(positions [S], seen [S, S]) of a clean sequence of `n_clean`
    tokens followed by len(starts) noised copies of a block each, copy
    c at positions starts[c] .. + B - 1: a clean row sees the clean
    keys block-causally and no copy; a copy's row sees the clean keys
    before its block and the keys of its own copy."""
    b = hp["block_length"]
    starts = np.asarray(starts, np.int64)
    positions = np.concatenate(
        [np.arange(n_clean), (starts[:, None] + np.arange(b)).reshape(-1)])
    copy = np.concatenate([np.full(n_clean, -1),
                           np.repeat(np.arange(len(starts)), b)])
    clean_key = copy[None, :] < 0
    seen = np.where(
        copy[:, None] < 0,
        clean_key & (positions[None, :] // b <= positions[:, None] // b),
        (clean_key & (positions[None, :] // b < positions[:, None] // b))
        | (copy[None, :] == copy[:, None]))
    return positions, seen


def noised_logits(params, clean, starts, blocks, hp, layer_by_layer=False):
    """The logits [n, B, vocab] of n blocks `blocks` [n, B] (tokens, the
    mask token where a position is open), each run at positions
    starts[c] on against the prefix clean[:starts[c]] (`starts`
    multiples of B, none past len(clean)): one pass over `clean`
    followed by the blocks (`noised_inputs`). With `layer_by_layer`
    one jitted call a layer, handed the run's stacks where they lie
    (beside a deployment that fills the chip), and a numpy result."""
    clean = np.asarray(clean, np.int32)
    blocks = np.asarray(blocks, np.int32).reshape(len(starts), -1)
    assert all(s % hp["block_length"] == 0 and s <= len(clean)
               for s in starts), starts
    positions, seen = noised_inputs(len(clean), starts, hp)
    tokens = jnp.asarray(np.concatenate([clean, blocks.reshape(-1)]))
    positions, seen = jnp.asarray(positions, jnp.int32), jnp.asarray(seen)
    if not layer_by_layer:
        out = sequence_logits(params, tokens, hp, positions, seen)
        return out[len(clean):].reshape(blocks.shape + (-1,))
    one_block = jax.jit(functools.partial(block, hp=hp))
    x = jax.jit(lambda e, t: _f32(e[t]))(params["embed"], tokens)
    for run, i in layers_of(params):
        x = one_block(x, run, jnp.int32(i), positions, seen)
    top = {k: v for k, v in params.items() if k != "runs"}
    out = jax.jit(functools.partial(head, hp=hp))(top, x[len(clean):])
    return np.asarray(out).reshape(blocks.shape + (-1,))


def denoise_logits(params, prefix, block_tokens, hp):
    """The logits [B, vocab] of one block run against the committed
    `prefix` (a whole number of blocks): one denoising step's."""
    return noised_logits(params, prefix, [len(prefix)], [block_tokens],
                         hp)[0]


def fix_most_confident(logits, is_open, n):
    """(x0 [B], which positions a denoising step fixes [B] bool): of
    the open positions the `n` whose greedy token has the highest
    softmax probability, or those that are left; ties to the lower
    position."""
    logits = np.asarray(logits, np.float64)
    x0 = logits.argmax(-1)
    shifted = logits - logits.max(-1, keepdims=True)
    confidence = 1.0 / np.exp(shifted).sum(-1)
    order = sorted(np.nonzero(is_open)[0],
                   key=lambda p: (-np.float32(confidence[p]), p))
    fixed = np.zeros(len(is_open), bool)
    fixed[order[:n]] = True
    return x0, fixed


def generate(params, prompt, max_tokens, hp, denoising_steps=None, stop=()):
    """Greedy generation by diffusion over blocks, the loop of the
    module's docstring with no cache: (tokens, fixed_at), the answer's
    tokens and for each the denoising step of its block at which it
    was fixed. Every step is one pass over the prefix so far (in a
    buffer of the answer's full length, so that one program serves
    them all: a block never sees what lies at or after its own
    positions in it) and the block."""
    b, mask = hp["block_length"], hp["mask_token_id"]
    n = b // (denoising_steps or hp["denoising_steps"])
    prompt = [int(t) for t in prompt]
    whole = len(prompt) // b * b
    prefix, known = prompt[:whole], prompt[whole:]
    room = -(-(len(prompt) + max(max_tokens, 1)) // 32) * 32
    run = jax.jit(functools.partial(sequence_logits, hp=hp))
    tokens, fixed_at = [], []
    while True:
        block_tokens = np.array(known + [mask] * (b - len(known)), np.int32)
        is_open = np.arange(b) >= len(known)
        steps = np.full(b, -1)
        step = 0
        clean = np.zeros(room, np.int32)
        clean[:len(prefix)] = prefix
        positions, seen = noised_inputs(room, [len(prefix)], hp)
        while is_open.any():
            logits = run(params, jnp.asarray(np.concatenate(
                [clean, block_tokens])), positions=jnp.asarray(positions),
                seen=jnp.asarray(seen))[room:]
            x0, fixed = fix_most_confident(logits, is_open, n)
            block_tokens = np.where(fixed, x0, block_tokens).astype(np.int32)
            steps[fixed] = step
            is_open &= ~fixed
            step += 1
        for p in range(len(known), b):
            tokens.append(int(block_tokens[p]))
            fixed_at.append(int(steps[p]))
            if tokens[-1] in stop or len(tokens) >= max_tokens:
                return tokens, fixed_at
        prefix, known = prefix + block_tokens.tolist(), []


def replay(params, prompt, tokens, fixed_at, hp, layer_by_layer=True,
           room=0):
    """What the model must have been shown at every denoising step of
    a served answer, and the reference's logits there. `tokens` and
    `fixed_at` are the answer and each token's step as served; only
    the answer's whole blocks can be rebuilt (of a block the request
    ended inside, the dropped positions are not known), so the caller
    asks for answers that end on a block's edge. Returns a list, a
    pass each in the order they ran, of dicts: `start` (the block's
    first position), `open` [B] bool (the positions open going in),
    `fixed` [B] bool (those the pass fixed), `tokens` [B] (the block's
    final tokens) and `logits` [B, vocab] float32 of the pass's input
    (the mask token at the open positions) against the clean prefix
    before the block. With `room` the one pass over them all is padded
    to that many passes (copies of the first that are dropped), so that
    answers of different lengths run one compiled program."""
    b, mask = hp["block_length"], hp["mask_token_id"]
    prompt = [int(t) for t in prompt]
    clean = prompt + [int(t) for t in tokens]
    # A prompt token is known before any step.
    steps = [-1] * len(prompt) + [int(s) for s in fixed_at]
    whole = len(prompt) // b * b
    passes = []
    for start in range(whole, len(clean) // b * b, b):
        final = np.asarray(clean[start:start + b], np.int32)
        at = np.asarray(steps[start:start + b])
        for step in range(at.max() + 1):
            passes.append({"start": start, "open": at >= step,
                           "fixed": at == step, "tokens": final})
    if not passes:
        return passes
    padded = passes + passes[:1] * (room - len(passes))
    logits = noised_logits(
        params, clean, [p["start"] for p in padded],
        [np.where(p["open"], mask, p["tokens"]) for p in padded], hp,
        layer_by_layer=layer_by_layer)
    for p, rows in zip(passes, logits):
        p["logits"] = rows
    return passes
