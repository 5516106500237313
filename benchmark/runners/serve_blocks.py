"""A serving cell of a model that generates by diffusion over blocks:
`runners/serve.py`'s deployment, load, window and verdict on the
window's requests, with checks of its own kind. That file's two checks
are autoregressive (a decode step of one token against a causal
forward pass; token i against the reference's next-token choice at
i - 1) and cannot judge a model whose step runs a block of positions
that see each other, fixes some of them and predicts a position from
its own logits. Here:

(a) logits through the cache against the plain reference, on a shallow
    copy of the model at the published widths: a block-causal prefill
    of rows padded to the engine's bucket, then block steps from each
    row's own length, every block once as a denoising pass (the mask
    token at some positions) and once as its commit;
(b) one greedy prompt asked three times. The first is prefilled whole,
    through the flash kernel, and fills the prefix cache; the others
    copy its blocks of rows in and prefill the tail through the plain
    attention, another program, whose keys differ from the first's in
    a last bit of bfloat16, and a near-tie between two open positions
    or two tokens may then fall the other way. So the two cached
    answers have to be equal in tokens and steps (one program, run
    twice), and a cached answer is held to the reference as the cold
    ones of (c) are, under the same margins: both ways into the cache
    stand against the reference, and how far the cold and the cached
    answer agree is printed. Greedy output is not stable across a
    prefix-cache hit for this family, where `runners/serve.py` holds
    exactly that for the five others;
(c) several prompts asked together: every pass of every block of
    their answers is rebuilt from the served tokens and the step each
    was fixed at (`references/sdar_moe.py` `replay`), over the
    deployment's own weights: a fixed token has to be the reference's
    choice at its position or lie under it by no more than a margin,
    the positions a pass fixed have to be the reference's most
    confident open ones or lie under the best one it passed over by no
    more than a margin, and the steps have to be the schedule's (from 0
    up, `block_length / denoising_steps` positions a step, what is
    left in the last). The first of them is the prompt of (b), so its
    answer comes from the prefix cache and the others' are cold;
(d) every request of the window that ended returned the tokens it
    asked for, inside the vocabulary, and the request stream did not
    run out.

A traced run's stretch is as long as the host's clock says
(`runners/serve.py` `measure`: from after `start_trace` returns to
before `stop_trace` is called) or, where the profile's own device
events span a little more, as long as they span: the profiler records
a little on either side of the host's marks, this cell's device is
busy 99.8 % of the stretch, and the seconds an operation ran must not
come to more than the stretch they are laid against
(`widen_to_the_profile`; by `_WIDEN_MAX_S` at most, and a profile that
overruns the marks by more fails the run; the amount is on the line).
"""

from __future__ import annotations

import functools
import gc
import http.client
import json
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.harness import device as hw
from benchmark.harness import trace as tr
from benchmark.harness.manifest import model_adapter, plugin
from benchmark.runners.serve import (NEEDS, ROUTE, Deployment, judge,
                                     measure, probes)
from benchmark.runners.train import prng_key

# Which positions of a block a denoising pass of check (a) finds open,
# by row and block in turn: one to four of them, never none.
_OPEN = np.array([[0, 1, 0, 1], [1, 1, 1, 0], [0, 0, 0, 1], [1, 0, 1, 1],
                  [1, 1, 1, 1], [0, 1, 1, 0], [1, 0, 0, 0]], bool)


def check_against_reference(config, seed, served=None):
    """Check (a). Every row is prefilled with the same number of
    tokens, padded as the engine pads a prompt, and then steps block by
    block from its own, shorter length (`reference_prompt_lens`), so
    the rows of a block step stand at different positions and phases
    and behind a short row's position the cache holds keys of tokens
    the row has not reached. `served` stands in for the adapter's
    `cached_forward` in tests. Returns (largest error over largest
    |reference| logit, positions compared)."""
    import jax
    import jax.numpy as jnp

    plan = config["serve"]
    model = model_adapter(config, NEEDS)
    reference = plugin("references", config["reference"])
    hp = reference.hyper(config)
    b, mask = hp["block_length"], hp["mask_token_id"]
    small = model.with_layers(model.program_config(config),
                              plan["reference_layers"])
    params = jax.jit(functools.partial(model.init, small))(prng_key(seed))
    lens = np.asarray(plan["reference_prompt_lens"])
    rows, n_pre, n_blocks = len(lens), int(lens.max()), \
        plan["reference_block_steps"]
    assert not (lens % b).any(), lens
    tokens = np.random.default_rng([seed, 7]).integers(
        0, config["vocab_size"], (rows, n_pre + n_blocks * b),
        dtype=np.int32)
    at = np.arange(rows)[:, None]
    starts = lens[:, None] + b * np.arange(n_blocks)          # [rows, blocks]
    is_open = _OPEN[(np.arange(rows)[:, None] + rows * np.arange(n_blocks))
                    % len(_OPEN)]                          # [rows, blocks, b]
    final = tokens[at[..., None], starts[..., None] + np.arange(b)]
    noised = np.where(is_open, mask, final).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(functools.partial(
            reference.forward, hp=hp))(params, jnp.asarray(tokens)))
        # A row's noised blocks against its own clean tokens, all in
        # one pass (a copy sees the clean keys before its block only).
        want_noised = np.stack([
            reference.noised_logits(params, tokens[r], starts[r], noised[r],
                                    hp, layer_by_layer=True)
            for r in range(rows)])                  # [rows, blocks, b, vocab]
    step = jax.jit(functools.partial(served or model.cached_forward,
                                     cfg=small))
    cache = model.init_cache(small, rows, plan["max_seq_len"])
    logits, cache = step(params, jnp.asarray(tokens[:, :n_pre]),
                         cache=cache, start_pos=jnp.zeros(rows, jnp.int32))
    worst = np.abs(np.asarray(logits.astype(jnp.float32))
                   - want[:, :n_pre]).max()
    for i in range(n_blocks):
        start = jnp.asarray(starts[:, i], jnp.int32)
        for fed, wanted in (
                (noised[:, i], want_noised[:, i]),
                (final[:, i], want[at, starts[:, i, None] + np.arange(b)])):
            logits, cache = step(params, jnp.asarray(fed), cache=cache,
                                 start_pos=start)
            worst = max(worst, np.abs(
                np.asarray(logits.astype(jnp.float32)) - wanted).max())
    return float(worst / np.abs(want).max()), \
        rows * (n_pre + 2 * n_blocks * b)


def ask(host, port, body):
    """One streamed request with a plain blocking client: its answer as
    [(token, step)], the stream's events in order."""
    conn = http.client.HTTPConnection(host, port, timeout=300)
    try:
        conn.request("POST", ROUTE, body=json.dumps(body),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        data = resp.read()
    finally:
        conn.close()
    if resp.status != 200:
        raise RuntimeError(f"status {resp.status}: {data[:300]!r}")
    events = [json.loads(e[len(b"data: "):]) for e in data.split(b"\n\n")
              if e.startswith(b"data: ") and e != b"data: [DONE]"]
    assert [e["index"] for e in events] == list(range(len(events)))
    return [(e["token"], e["step"]) for e in events]


def ask_all(host, port, bodies):
    """Every body posted at once: their answers, or what went wrong
    (judged as a wrong answer)."""
    def one(body):
        try:
            return ask(host, port, body)
        except Exception as e:
            return repr(e)

    with ThreadPoolExecutor(len(bodies)) as pool:
        return list(pool.map(one, bodies))


def steps_follow_the_schedule(passes, n):
    """Whether the passes of one answer (`replay`'s, in order) are the
    schedule's: a block's steps run from 0 up with no gap, and each
    fixes `n` of the positions open going in, or what is left."""
    return all(p["fixed"].sum() == min(n, p["open"].sum())
               and not (p["fixed"] & ~p["open"]).any() for p in passes)


def check_served_blocks(config, params, asked, answers, sized_for=None):
    """Check (c): what the deployment answered over HTTP, with the
    requests in its slots together, against the reference's `replay`
    over the deployment's own weights. Returns a dict: `token_short`,
    the worst shortfall of a fixed token's reference logit under the
    largest at its position, and `confidence_short`, the worst
    shortfall of a fixed position's reference log-confidence under the
    best open position the pass did not fix, both over the largest
    |reference| logit; `same`, the share of fixed tokens that are the
    reference's own choice; `schedule`, whether every answer's steps
    are the schedule's; `compared`, the tokens compared. `sized_for`:
    the requests whose longest answer sizes the replay's one compiled
    program, where `asked` is a part of them."""
    import jax

    reference = plugin("references", config["reference"])
    hp = reference.hyper(config)
    n = hp["block_length"] // hp["denoising_steps"]
    bad = {"token_short": float("inf"), "confidence_short": float("inf"),
           "same": 0.0, "schedule": False, "compared": 0}
    for body, answer in zip(asked, answers):
        if not isinstance(answer, list) or \
                len(answer) != body["max_tokens"]:
            return bad
    token_short = confidence_short = 0.0
    same = compared = 0
    # The passes of the longest answer: every replay is padded to them.
    room = max(-(-body["max_tokens"] // hp["block_length"]) + 1
               for body in sized_for or asked) * hp["denoising_steps"]
    schedule = True
    for body, answer in zip(asked, answers):
        tokens, steps = zip(*answer)
        with jax.default_matmul_precision("highest"):
            passes = reference.replay(params, body["prompt_ids"], tokens,
                                      steps, hp, room=room)
        schedule &= bool(passes) and steps_follow_the_schedule(passes, n)
        scale = max(np.abs(p["logits"]).max() for p in passes)
        for p in passes:
            top = p["logits"].max(-1)                                  # [B]
            chosen = p["logits"][np.arange(len(top)), p["tokens"]]
            shifted = p["logits"] - top[:, None]
            # log softmax(logits)[argmax]
            confidence = -np.log(np.exp(shifted).sum(-1))
            fixed, passed = p["fixed"], p["open"] & ~p["fixed"]
            if not fixed.any():  # not the schedule's, and said so above
                continue
            token_short = max(token_short, float(
                (top - chosen)[fixed].max() / scale))
            if passed.any():
                confidence_short = max(confidence_short, float(
                    (confidence[passed].max() - confidence[fixed].min())
                    / scale))
            same += int((p["logits"].argmax(-1) == p["tokens"])[fixed].sum())
            compared += int(fixed.sum())
        del passes
        gc.collect()
    return {"token_short": token_short, "confidence_short": confidence_short,
            "same": same / max(compared, 1), "schedule": schedule,
            "compared": compared}


# The most the profile may overrun the host's marks: three traced runs
# read 0, 3.4 and 0 ms (PERF.md section 7, PR 50). More is a stretch
# that does not match its profile, not the profiler's slack.
_WIDEN_MAX_S = 0.05


def widen_to_the_profile(out, trace_dir):
    """`trace_t1` moved out to where `trace_t0` plus the longest span of
    a device's recorded events ends, if that is later: the traced
    stretch is never shorter than the profile taken of it (a
    microsecond longer, so that a sum and a difference of floats cannot
    make it shorter). Returns the seconds it moved."""
    events = tr.load_xplane(tr.find_xplane(trace_dir))
    spans = [max(s + d for _, s, d in evs) - min(s for _, s, _ in evs)
             for dev in events["devices"].values()
             for evs in [dev["ops"] or dev["modules"]] if evs]
    widened = max([out["trace_t0"] + span / 1e9 + 1e-6 - out["trace_t1"]
                   for span in spans] + [0.0])
    assert widened <= _WIDEN_MAX_S, \
        f"the profile spans {widened:.3f} s more than the traced stretch"
    out["trace_t1"] += widened
    return widened


def run(cell, *, seed, seconds, trace_dir, devices, run_dir):
    config = cell.config
    plan = config["serve"]
    phases = hw.Phases()
    phases.mark("imports")
    err, positions = check_against_reference(config, seed)
    gc.collect()
    phases.mark("reference check")
    dep = Deployment(cell, seed)
    phases.mark("deploy")
    try:
        asked = probes(config, seed)
        cold, *twice = [ask(dep.host, dep.port, asked[0]) for _ in range(3)]
        together = ask_all(dep.host, dep.port, asked)
        params = dep.params.pop()
        served = check_served_blocks(config, params, asked, together)
        cached = check_served_blocks(config, params, asked[:1], twice[:1],
                                     sized_for=asked)
        del params
        gc.collect()
        phases.mark("probes")
        out = measure(dep, cell, seed=seed, seconds=seconds,
                      trace_dir=trace_dir, run_dir=run_dir)
        after = dep.stats()
        out["memory"] = [hw.memory(devices[0])]
    finally:
        dep.close()
    widened = widen_to_the_profile(out, trace_dir) if trace_dir else 0.0
    verdict = judge(out, config["vocab_size"])
    checks = {
        "prefill, denoising and commit logits within tolerance of the "
        "reference": err <= plan["logit_tolerance"],
        "a greedy prompt asked twice gives the same tokens and steps":
            twice[0] == twice[1]
            and len(twice[0]) == asked[0]["max_tokens"],
        "the answer from the prefix cache is the reference's within both "
        "margins, on schedule":
            cached["compared"] > 0 and cached["schedule"]
            and cached["token_short"] <= plan["served_token_margin"]
            and cached["confidence_short"] <= plan["confidence_margin"],
        "served tokens are the reference's choice within the margin":
            served["compared"] > 0
            and served["token_short"] <= plan["served_token_margin"],
        "the positions fixed are the reference's most confident within "
        "the margin":
            served["confidence_short"] <= plan["confidence_margin"],
        "the steps of every block are the schedule's": served["schedule"],
        "every request that ended returned the tokens it asked for":
            verdict["failed"] == 0 and verdict["attempted"] > 0,
        "the request stream did not run out": not verdict["exhausted"],
    }
    out.update(verdict)
    slots = [a for a, _ in out["engine_samples"]]
    queued = [q for _, q in out["engine_samples"]]
    totals = after["totals"]
    forwards = totals["slot_forwards_denoise"] + totals["slot_forwards_commit"]
    out.update(
        kind="serve_blocks", checks=checks, n_slots=dep.n_slots,
        warmup_s=dep.deploy_stats["warmup_s"],
        compiled_programs=dep.deploy_stats["compiled_programs"],
        kv_cache=after.get("kv_cache"), totals=totals,
        log=(f"reference: max logit error {err:.4f} of max |logit| over "
             f"{positions} positions (tolerance {plan['logit_tolerance']}); "
             f"of {served['compared']} served tokens {served['same']:.1%} "
             f"are the reference's choice, worst {served['token_short']:.4f} "
             f"of max |logit| under it (margin "
             f"{plan['served_token_margin']}), a fixed position's "
             f"log-confidence at worst {served['confidence_short']:.4f} of "
             f"max |logit| under a passed-over one (margin "
             f"{plan['confidence_margin']}), steps on schedule "
             f"{served['schedule']}; the prompt asked cold and then twice "
             f"from the prefix cache: the two agree {twice[0] == twice[1]}, "
             f"the cold answer with them in "
             f"{sum(a == b for a, b in zip(cold, twice[0]))} of {len(cold)} "
             f"tokens and steps; of the cached answer's "
             f"{cached['compared']} tokens {cached['same']:.1%} are the "
             f"reference's choice, worst {cached['token_short']:.4f} under "
             f"it, log-confidence at worst {cached['confidence_short']:.4f} "
             f"under a passed-over one, steps on schedule "
             f"{cached['schedule']}; the traced stretch widened to the "
             f"profile by {widened * 1e3:.3f} ms; "
             f"warm-up {dep.deploy_stats['warmup_s']:.2f} s, "
             f"{dep.deploy_stats['compiled_programs']} programs; requests "
             f"ended {verdict['attempted']} (failed {verdict['failed']}), "
             f"finished in the window {verdict['finished_in_window']}, due "
             f"in the window {verdict['due_in_window']}; first token after "
             f"{verdict['ttft_ms']} ms, token gaps {verdict['tpot_ms']} ms; "
             f"since the deploy {forwards} slot forwards, "
             f"{totals['slot_forwards_commit']} of them commits, "
             f"{totals['tokens_fixed']} tokens fixed, "
             f"{totals['blocks_emitted']} blocks; experts touched "
             f"{totals.get('experts_touched', 0)} of "
             f"{totals.get('experts_held_steps', 0)} held x layers x "
             f"forwards; generator late "
             f"p50 {verdict['generator_late_p50_ms']:.2f} ms max "
             f"{verdict['generator_late_max_ms']:.2f} ms; slots active "
             f"mean {np.mean(slots) if slots else 0:.1f} of {dep.n_slots}, "
             f"queued mean {np.mean(queued) if queued else 0:.1f} max "
             f"{max(queued, default=0)}; failures "
             f"{verdict['first_failures']}; stage spans read "
             f"{ {k: len(v) for k, v in out['stages'].items()} }; set-up: "
             f"{phases}, then the ramp to the window"))
    return out
