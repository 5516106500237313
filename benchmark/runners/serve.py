"""A serving cell: `LLMDeployment` behind the HTTP proxy under the
default `ray_config`, as `chip_smoke.serve_phase` showed it runs on the
chip, driven by the load generator (a child process that never touches
JAX).

Set-up: the cell's chip; prefill and decode through the cache, rows at
different positions as in the engine's slots, against the plain
reference's full forward pass, on a shallow copy of the model at the
published widths; the deployment (weights made on the device in one
jitted call from the seed, warm-up of the mix's own prefill buckets);
one greedy prompt asked twice, then several together, whose served
tokens are held against the reference over the deployment's own
weights; the load generator's ramp to steady state. Then the window opens: `t0` is only a mark in time, the
clients were started before it and run on after it, and everything is
counted by when tokens reached the client. A traced run measures half
the window untraced and then traces a few seconds of the same load.
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import http.client
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

from benchmark.harness import device as hw
from benchmark.harness import traffic
from benchmark.harness.manifest import BENCH_DIR, model_adapter, plugin
from benchmark.runners.train import prng_key

ROUTE = "/llm"
# What a serving cell takes from its family's model adapter.
NEEDS = ("program_config", "with_layers", "init", "cached_forward",
         "init_cache", "deployment_args")


def check_against_reference(config, seed, served=None):
    """Logits through the cache, at the shape the engine serves, against
    the reference's full forward pass, on `reference_layers` layers at
    the published widths. One row a slot: every row is prefilled with
    the same number of tokens and then decodes from its own length
    (`reference_prompt_lens`), so the rows of a decode step stand at
    different positions, and behind a short row's position the cache
    holds keys of tokens that row has not reached (as a slot that was
    used before does): a wrong mask, rows mixed up or positions off by
    one all show. (At the published widths the initialiser's weights
    give query-key scores a spread of 1.6, so attention weighs in the
    logits as it is.) `served` stands in for the adapter's
    `cached_forward` in tests.
    Returns (largest error over largest |reference| logit, positions
    compared)."""
    import jax
    import jax.numpy as jnp

    plan = config["serve"]
    model = model_adapter(config, NEEDS)
    reference = plugin("references", config["reference"])
    small = model.with_layers(model.program_config(config),
                              plan["reference_layers"])
    params = jax.jit(functools.partial(model.init, small))(prng_key(seed))
    lens = np.asarray(plan["reference_prompt_lens"])
    rows, n_pre, n_dec = len(lens), int(lens.max()), \
        plan["reference_decode_steps"]
    tokens = np.random.default_rng([seed, 7]).integers(
        0, config["vocab_size"], (rows, n_pre + n_dec), dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(functools.partial(
            reference.forward, hp=reference.hyper(config)))(
                params, jnp.asarray(tokens)))
    step = jax.jit(functools.partial(served or model.cached_forward,
                                     cfg=small))
    cache = model.init_cache(small, rows, plan["max_seq_len"])
    logits, cache = step(params, jnp.asarray(tokens[:, :n_pre]),
                         cache=cache, start_pos=jnp.zeros(rows, jnp.int32))
    worst = np.abs(np.asarray(logits.astype(jnp.float32))
                   - want[:, :n_pre]).max()
    at = np.arange(rows)
    for i in range(n_dec):
        logits, cache = step(
            params, jnp.asarray(tokens[at, lens + i][:, None]), cache=cache,
            start_pos=jnp.asarray(lens + i, jnp.int32))
        worst = max(worst, np.abs(
            np.asarray(logits[:, 0].astype(jnp.float32))
            - want[at, lens + i]).max())
    return float(worst / np.abs(want).max()), rows * (n_pre + n_dec)


def probes(config, seed):
    """The greedy requests whose answers are held against the
    reference: prompts of different lengths, each asking for as many
    tokens as fill `probe_total`."""
    plan = config["serve"]
    rng = np.random.default_rng([seed, 11])
    vocab = config["vocab_size"]
    return [{"prompt_ids": rng.integers(0, vocab, n).tolist(),
             "max_tokens": plan["probe_total"] - n, "stream": True,
             "temperature": 0.0} for n in plan["probe_prompt_lens"]]


def ask_together(host, port, bodies):
    """Every body posted at once, a thread each: their answers."""
    answers = [None] * len(bodies)

    def ask(i):
        try:
            answers[i] = post(host, port, bodies[i])
        except Exception as e:  # judged below: no answer is a wrong one
            answers[i] = repr(e)

    threads = [threading.Thread(target=ask, args=(i,))
               for i in range(len(bodies))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return answers


def check_served_tokens(config, params, asked, answers):
    """What the deployment answered over HTTP, with the requests in its
    slots together, against the reference's full forward pass over the
    deployment's own weights: each served token has to be the
    reference's greedy choice at its position, or (bfloat16 against
    float32 can swap two near-equal logits) lie under the reference's
    largest logit by no more than a margin. Returns (worst shortfall
    over the largest |reference| logit, share of tokens that are the
    reference's own choice, tokens compared)."""
    import jax
    import jax.numpy as jnp

    reference = plugin("references", config["reference"])
    for body, tokens in zip(asked, answers):
        if not isinstance(tokens, list) or \
                len(tokens) != body["max_tokens"]:
            return float("inf"), 0.0, 0
    sequences = [jnp.asarray((b["prompt_ids"] + t)[:-1], jnp.int32)
                 for b, t in zip(asked, answers)]
    with jax.default_matmul_precision("highest"):
        logits = [np.asarray(x) for x in reference.logits_layer_by_layer(
            params, sequences, reference.hyper(config))]
    short, same, n = 0.0, 0, 0
    for body, tokens, lg in zip(asked, answers, logits):
        first = len(body["prompt_ids"]) - 1
        rows = lg[first:first + len(tokens)]
        chosen = rows[np.arange(len(tokens)), tokens]
        short = max(short, float((rows.max(-1) - chosen).max()
                                 / np.abs(lg).max()))
        same += int((rows.argmax(-1) == np.asarray(tokens)).sum())
        n += len(tokens)
    return short, same / n, n


def post(host, port, body):
    """One streamed request with a plain blocking client: its tokens."""
    conn = http.client.HTTPConnection(host, port, timeout=120)
    try:
        conn.request("POST", ROUTE, body=json.dumps(body),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        data = resp.read()
    finally:
        conn.close()
    if resp.status != 200:
        raise RuntimeError(f"status {resp.status}: {data[:300]!r}")
    events = [e[len(b"data: "):] for e in data.split(b"\n\n")
              if e.startswith(b"data: ")]
    return [json.loads(e)["token"] for e in events if e != b"[DONE]"]


class Deployment:
    """The system under test, deployed; `close()` takes it down."""

    def __init__(self, cell, seed):
        import jax

        import ray_tpu
        from ray_tpu import serve
        from ray_tpu._private.config import RayTpuConfig, ray_config
        from ray_tpu.serve.llm import LLMDeployment

        if dataclasses.asdict(ray_config) != \
                dataclasses.asdict(RayTpuConfig()):
            raise RuntimeError("ray_config is not the default")
        config, mix = cell.config, cell.traffic
        plan = config["serve"]
        model = model_adapter(config, NEEDS)
        cfg = model.program_config(config)
        self.vocab = config["vocab_size"]
        self.n_slots = plan["max_batch_size"]
        self.ray_tpu, self.serve = ray_tpu, serve
        if not ray_tpu.is_initialized():  # a CPU rehearsal names its devices
            ray_tpu.init()

        self.params = []  # the replica's own weights, for the reference

        def params_fn():
            self.params.append(jax.jit(functools.partial(model.init, cfg))(
                prng_key(seed)))
            return self.params[-1]

        args, kwargs = model.deployment_args(cfg, params_fn)
        self.handle = serve.run(
            serve.deployment(LLMDeployment).bind(
                *args, **kwargs, max_batch_size=self.n_slots,
                max_seq_len=plan["max_seq_len"],
                warmup_max_prompt_len=traffic.longest_prompt(mix)),
            route_prefix=ROUTE)
        proxy = serve.start_http_proxy()
        self.host, self.port = proxy.host, proxy.port
        self.deploy_stats = self.stats()  # returns when it can serve

    def stats(self):
        return self.ray_tpu.get(self.handle.stats.remote())

    def close(self):
        self.serve.shutdown()
        self.ray_tpu.shutdown()


class Load:
    """The load generator's process."""

    def __init__(self, dep, mix, seed, run_dir, n_requests):
        plan = traffic.request_stream(mix, seed, n_requests)
        plan.update(host=dep.host, port=dep.port, route=ROUTE,
                    vocab=dep.vocab,
                    clients=mix.get("clients_per_slot", 0) * dep.n_slots)
        self.plan_path = os.path.join(run_dir, "plan.json")
        self.out_path = os.path.join(run_dir, "records.json")
        with open(self.plan_path, "w") as f:
            json.dump(plan, f)
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(BENCH_DIR, "harness", "loadgen.py"),
             self.plan_path, self.out_path],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            env={k: v for k, v in os.environ.items()
                 if not k.startswith(("JAX_", "XLA_", "TPU_"))})
        self.t_start = json.loads(self.proc.stdout.readline())["started"]

    def stop(self, drain_s):
        """Stop sending, give requests in flight `drain_s` to get their
        first token, and return what the generator recorded. The process
        has ended on return."""
        try:
            self.proc.stdin.write(f"stop {drain_s}\n")
            self.proc.stdin.flush()
            self.proc.wait(timeout=drain_s + 30)
        finally:
            self.close()
        if self.proc.returncode != 0:
            raise RuntimeError(
                f"load generator exited with {self.proc.returncode}")
        with open(self.out_path) as f:
            return json.load(f)

    def close(self):
        """The process is gone on return, whatever state it was in."""
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()


def sleep_until(t):
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        time.sleep(min(left, 0.2))


def wait_steady(dep, load, mix):
    """Steady state: the ramp has run its length and, in a closed loop,
    every slot is occupied and has emitted a token (the engine counts a
    slot active from its first token on)."""
    sleep_until(load.t_start + mix["ramp_s"])
    if mix["loop"] != "closed":
        return
    deadline = time.perf_counter() + 30
    while dep.stats()["active_slots"] < dep.n_slots:
        if time.perf_counter() > deadline:
            raise RuntimeError("the slots never all filled")
        time.sleep(0.02)


class StageSpans:
    """The program's stage spans (PR 18 `critical_path.record_stage`),
    read from the flight recorder's ring while the run goes: the ring
    keeps the last few hundred spans of the process, each as it was
    recorded."""

    def __init__(self):
        self.seen = {}
        self.clock_offset = time.time() - time.perf_counter()

    def poll(self):
        from ray_tpu._private import flight_recorder

        for s in flight_recorder.local_snapshot()["spans"]:
            self.seen[(s["t"], s["trace_id"], s["stage"])] = s["dur_s"]

    def recorded_between(self, t0, t1):
        """{stage: [seconds, ...]} of the spans recorded in [t0, t1) of
        `time.perf_counter`."""
        out = {}
        for (t, _, stage), dur in self.seen.items():
            if t0 <= t - self.clock_offset < t1:
                out.setdefault(stage, []).append(dur)
        return out


def measure(dep, cell, *, seed, seconds, trace_dir, run_dir):
    """Ramp, window, optional traced stretch, stop. Returns the raw
    material of the metrics."""
    import jax

    mix = cell.traffic
    if mix["loop"] == "open":
        horizon = mix["ramp_s"] + seconds + mix["trace_s"] + 5
        n_requests = int(traffic.rate_rps(mix) * horizon * 1.5) + 64
    else:
        n_requests = 8192
    spans = StageSpans()
    load = Load(dep, mix, seed, run_dir, n_requests)
    try:
        wait_steady(dep, load, mix)
        t0 = time.perf_counter()
        t1 = t0 + (seconds / 2 if trace_dir else seconds)
        engine = []
        while time.perf_counter() < t1:
            sleep_until(min(t1, time.perf_counter() + 0.25))
            spans.poll()
            s = dep.stats()
            engine.append((s["active_slots"], s["queued"]))
        t1 = time.perf_counter()
        out = {"window": (t0, t1), "engine_samples": engine}
        if trace_dir:
            jax.profiler.start_trace(trace_dir)
            t_trace = time.perf_counter()
            sleep_until(t_trace + mix["trace_s"])
            t_end = time.perf_counter()
            jax.profiler.stop_trace()
            out.update(trace_t0=t_trace, trace_t1=t_end)
        result = load.stop(mix["drain_s"])
    finally:
        load.close()
    spans.poll()
    out.update(records=result["records"], offered=result["offered"],
               exhausted=result["exhausted"],
               stages=spans.recorded_between(t0, t1))
    return out


def judge(out, vocab):
    """`attempted`, `failed` and the checks of the window's answers."""
    ended = [r for r in out["records"] if r["t_end"] is not None
             and (r["done"] or r["error"] or r["status"] not in (None, 200))]
    bad = [r for r in ended if not (
        r["done"] and r["status"] == 200 and not r["error"]
        and len(r["tokens"]) == r["max_tokens"]
        and all(isinstance(t, int) and 0 <= t < vocab for t in r["tokens"]))]
    t0, t1 = out["window"]
    late = [r["sent"] - r["due"] for r in out["records"]
            if r["sent"] is not None and t0 <= r["due"] < t1]
    waits = traffic.first_token_waits(out["records"], t0, t1)
    gaps = traffic.gaps_in_window(out["records"], t0, t1)
    return {
        "ttft_ms": {f"p{round(100 * q)}": round(
            1e3 * (traffic.quantile(waits, q) or 0), 1)
            for q in (0.5, 0.9, 0.95, 0.99)},
        "tpot_ms": {f"p{round(100 * q)}": round(
            1e3 * (traffic.quantile(gaps, q) or 0), 2)
            for q in (0.5, 0.95, 0.99)},
        "attempted": len(ended), "failed": len(bad),
        "finished_in_window": sum(1 for r in ended if r["done"]
                                  and t0 <= r["t_end"] < t1),
        "due_in_window": len(late),
        "generator_late_p50_ms": 1e3 * (traffic.quantile(late, 0.5) or 0),
        "generator_late_max_ms": 1e3 * max(late, default=0),
        "first_failures": [
            {k: r[k] for k in ("index", "status", "error", "done")}
            | {"n_tokens": len(r["tokens"]), "asked": r["max_tokens"]}
            for r in bad[:5]],
        "exhausted": out["exhausted"],
    }


def run(cell, *, seed, seconds, trace_dir, devices, run_dir):
    config, mix = cell.config, cell.traffic
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
        twice = [post(dep.host, dep.port, asked[0]) for _ in range(2)]
        together = ask_together(dep.host, dep.port, asked)
        short, same, compared = check_served_tokens(
            config, dep.params.pop(), asked, together)
        gc.collect()
        phases.mark("probes")
        out = measure(dep, cell, seed=seed, seconds=seconds,
                      trace_dir=trace_dir, run_dir=run_dir)
        after = dep.stats()
        out["memory"] = [hw.memory(devices[0])]
    finally:
        dep.close()
    verdict = judge(out, config["vocab_size"])
    checks = {
        "prefill and decode logits within tolerance of the reference":
            err <= plan["logit_tolerance"],
        "a greedy prompt asked twice gives the same tokens":
            twice[0] == twice[1]
            and len(twice[0]) == asked[0]["max_tokens"],
        "served tokens are the reference's greedy choice within the margin":
            compared > 0 and short <= plan["served_token_margin"],
        "every request that ended returned the tokens it asked for":
            verdict["failed"] == 0 and verdict["attempted"] > 0,
        "the request stream did not run out": not verdict["exhausted"],
    }
    out.update(verdict)
    slots = [a for a, _ in out["engine_samples"]]
    queued = [q for _, q in out["engine_samples"]]
    out.update(
        kind="serve", checks=checks, n_slots=dep.n_slots,
        warmup_s=dep.deploy_stats["warmup_s"],
        compiled_programs=dep.deploy_stats["compiled_programs"],
        kv_cache=after.get("kv_cache"),
        log=(f"reference: max logit error {err:.4f} of max |logit| over "
             f"{positions} positions (tolerance {plan['logit_tolerance']}); "
             f"of {compared} served tokens {same:.1%} are the reference's "
             f"choice, worst {short:.4f} of max |logit| under it (margin "
             f"{plan['served_token_margin']}); "
             f"warm-up {dep.deploy_stats['warmup_s']:.2f} s, "
             f"{dep.deploy_stats['compiled_programs']} programs; requests "
             f"ended {verdict['attempted']} (failed {verdict['failed']}), "
             f"finished in the window {verdict['finished_in_window']}, due "
             f"in the window {verdict['due_in_window']}; first token after "
             f"{verdict['ttft_ms']} ms, token gaps {verdict['tpot_ms']} ms; "
             f"generator late "
             f"p50 {verdict['generator_late_p50_ms']:.2f} ms max "
             f"{verdict['generator_late_max_ms']:.2f} ms; slots active "
             f"mean {np.mean(slots) if slots else 0:.1f} of {dep.n_slots}, "
             f"queued mean {np.mean(queued) if queued else 0:.1f} max "
             f"{max(queued, default=0)}; failures "
             f"{verdict['first_failures']}; stage spans read "
             f"{ {k: len(v) for k, v in out['stages'].items()} }; set-up: "
             f"{phases}, then the ramp to the window"))
    return out
