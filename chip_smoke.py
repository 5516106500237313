"""The quickest proof that Train and Serve still start on the chip.

One process drives both of the system's main paths through the entry
points a user calls, at the widths of ``LlamaConfig.llama3_1b()`` with
seeded random weights, and checks what comes out:

- Train: ``ray_tpu.init()`` -> a ``ray_tpu.data`` dataset -> ``JaxTrainer``
  -> ``prepare_mesh`` / ``init_params_sharded`` / ``make_optimizer`` /
  ``make_train_step`` fed by ``iter_jax_batches``; the loss is finite and
  falls, and the compiled step holds the Pallas attention kernels.
- Serve: ``serve.run(LLMDeployment)`` under the default ``ray_config``
  -> ``serve.start_http_proxy()`` -> HTTP requests (SSE and unary,
  several prefill buckets, a shared prompt head); every answer is 200
  with the tokens asked for, greedy decoding repeats itself, and
  nothing compiles once warm-up is over.

Run it through the chip tool: ``python chip_smoke.py``. It refuses any
backend that is not a TPU and any failed phase is a non-zero exit. The
last two lines of stdout are ``summary {...}``, everything measured as
one JSON object, and ``{"ok": true, "device": {"platform", "kind",
"count"}}``, the device as JAX reports it. The phase functions take the
config and sizes as arguments so that ``tests/test_chip_smoke.py`` can
drive them at ``LlamaConfig.debug()`` on the CPU.
"""

from __future__ import annotations

import dataclasses
import faulthandler
import functools
import gc
import http.client
import json
import re
import sys
import time

import numpy as np

# max|kernel - reference| <= this share of max|reference|, in bf16 (8
# mantissa bits; both sides round p and the outputs to bf16).
_KERNEL_TOLERANCE = 2.0 ** -6


class CompileLog:
    """Every XLA compilation of this process, from jax's own monitoring
    events: (program, seconds) per backend compile — a persistent-cache
    load counts, with its load time — and the cache's hits and misses."""

    def __init__(self):
        import jax.monitoring

        self.compiles = []
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((kw.get("fun_name", "?"), seconds))

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache["misses"] += 1

    def mark(self):
        return len(self.compiles), dict(self.cache)

    def since(self, mark):
        n, cache = mark
        new = self.compiles[n:]
        return {"programs": len(new),
                "compile_s": round(sum(s for _, s in new), 2),
                "cache_hits": self.cache["hits"] - cache["hits"],
                "cache_misses": self.cache["misses"] - cache["misses"],
                "names": sorted({name for name, _ in new})}


def _memory(device):
    """HBM of one device: `in_use` is live arrays, `reserved` is what
    running programs take for their temporaries; the peaks are the
    process's."""
    stats = device.memory_stats() or {}  # the CPU backend reports none
    return {k: stats.get(k) for k in (
        "bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
        "peak_bytes_reserved", "bytes_limit")}


def attention_calls(hlo_text):
    """The Mosaic custom calls of a compiled program, by kernel name,
    each with the shapes it returns on one device."""
    calls = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        name = re.search(r'op_name="[^"]*?([^/"]+)/pallas_call"', line)
        shapes = re.findall(r"\b(?:bf16|f32)\[[\d,]+\]",
                            line.split(" custom-call(")[0])
        calls.setdefault(name.group(1) if name else "?", []).append(shapes)
    return calls


def check_flash_kernels(batch, seq, n_heads, n_kv_heads, head_dim):
    """Flash forward, dq and dk/dv, compiled at the train step's shapes,
    against `attention_reference` in bf16."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.attention import attention_reference, flash_attention

    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    q, do = (jax.random.normal(k, (batch, seq, n_heads, head_dim),
                               jnp.bfloat16) for k in keys[:2])
    k, v = (jax.random.normal(k, (batch, seq, n_kv_heads, head_dim),
                              jnp.bfloat16) for k in keys[2:])

    def reference(q, k, v):
        out = attention_reference(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), True, head_dim ** -0.5)
        return out.transpose(0, 2, 1, 3)

    def out_and_grads(fn):
        def run(q, k, v, do):
            out, pull = jax.vjp(fn, q, k, v)
            return (out,) + pull(do)
        return jax.jit(run)(q, k, v, do)

    got = out_and_grads(functools.partial(flash_attention, causal=True))
    want = out_and_grads(reference)
    report = {}
    for name, g, w in zip(("o", "dq", "dk", "dv"), got, want):
        g, w = (np.asarray(x.astype(jnp.float32)) for x in (g, w))
        assert np.isfinite(g).all(), f"flash {name} is not finite"
        err, scale = float(np.abs(g - w).max()), float(np.abs(w).max())
        assert err <= _KERNEL_TOLERANCE * scale, (
            f"flash {name}: max error {err} against max |reference| "
            f"{scale} exceeds {_KERNEL_TOLERANCE:g}")
        report[name] = {"max_err": err, "ref_max": scale}
    return report


def train_phase(cfg, *, batch, seq, steps, n_devices, compiles):
    """`steps` steps of the fsdp-sharded train step through JaxTrainer on
    `n_devices` devices, the dataset cycling one seeded block. Returns
    the loop's report; raises if the run is not right."""
    import ray_tpu
    from ray_tpu import data as rt_data
    from ray_tpu.air import session
    from ray_tpu.air.config import ScalingConfig
    from ray_tpu.train.jax_trainer import JaxTrainer, prepare_mesh

    def seeded_block(_ids):
        tokens = np.random.default_rng(0).integers(
            0, cfg.vocab_size, (batch, seq + 1), dtype=np.int32)
        return {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}

    dataset = rt_data.range(1, parallelism=1).map_batches(
        seeded_block, batch_size=None)
    scaling = ScalingConfig(
        num_workers=1, use_tpu=True,
        resources_per_worker={"TPU": n_devices},
        mesh={"data": 1, "fsdp": n_devices})

    def train_loop():
        import jax
        import jax.numpy as jnp

        from ray_tpu.models import (init_params_sharded, init_train_state,
                                    loss_fn, make_optimizer,
                                    make_train_step)
        from ray_tpu.parallel import named_sharding

        t_start = time.perf_counter()
        mark = compiles.mark()
        mesh = prepare_mesh(scaling)
        params = init_params_sharded(cfg, mesh, jax.random.PRNGKey(0))
        tx = make_optimizer(3e-4, warmup_steps=0,
                            moment_dtype=jnp.bfloat16)
        state = init_train_state(params, tx)
        step = make_train_step(
            lambda p, b: loss_fn(p, b, cfg, mesh=mesh), tx, mesh=mesh,
            batch_logical={"tokens": ("batch", "seq"),
                           "targets": ("batch", "seq")})
        shard = session.get_dataset_shard("train")
        batch_sharding = named_sharding(mesh, "batch", "seq")

        def epoch():  # the dataset is the one block: one batch an epoch
            return shard.iter_jax_batches(
                batch_size=batch, sharding=batch_sharding, drop_last=True)

        first = next(iter(epoch()))
        program = step.lower(state, first).compile()
        needs = program.memory_analysis()
        embed = params["embed"]
        report = {
            # What the step needs on one device: its arguments (the
            # state it updates in place, the batch) and, at its peak,
            # those plus its temporaries (activations, gradients, the
            # fused projection+CE blocks).
            "step_bytes": {"arguments": needs.argument_size_in_bytes,
                           "peak": needs.peak_memory_in_bytes},
            "granted_tpus": ray_tpu.cluster_resources().get("TPU", 0)
            - ray_tpu.available_resources().get("TPU", 0),
            "mesh": {k: v for k, v in mesh.shape.items() if v > 1},
            "mesh_devices": mesh.devices.size,
            "attention_calls": attention_calls(program.as_text()),
            "embed": {"global": embed.shape, "per_device": [
                s.data.shape for s in embed.addressable_shards]},
            "tokens": {"global": first["tokens"].shape, "per_device": [
                s.data.shape for s in first["tokens"].addressable_shards]},
        }

        losses, step_s = [], []
        while len(losses) < steps:
            for b in epoch():
                t0 = time.perf_counter()
                state, metrics = step(state, b)
                losses.append(float(metrics["loss"]))  # host fetch
                step_s.append(time.perf_counter() - t0)
                if len(losses) == 1:
                    report["compiled"] = compiles.since(mark)
                    mark = compiles.mark()
        report["loop_s"] = round(time.perf_counter() - t_start, 2)
        # Is block_until_ready a true barrier here? One more step timed
        # to it, then the fetch that should have nothing left to wait for.
        t0 = time.perf_counter()
        state, metrics = step(state, first)
        jax.block_until_ready(metrics["loss"])
        t_ready = time.perf_counter() - t0
        losses.append(float(metrics["loss"]))
        report["barrier"] = {
            "step_s_host_fetch": round(min(step_s[1:]), 4),
            "step_s_block_until_ready": round(t_ready, 4),
            "fetch_after_ready_s": round(
                time.perf_counter() - t0 - t_ready, 4)}
        report["compiled_after_first_step"] = compiles.since(mark)
        report["losses"] = [round(x, 4) for x in losses]
        report["memory"] = [_memory(d) for d in mesh.devices.flat]
        session.report({"smoke": report})

    result = JaxTrainer(train_loop, scaling_config=scaling,
                        datasets={"train": dataset}).fit()
    if result.error is not None:
        raise result.error
    report = result.metrics["smoke"]
    losses = report["losses"]
    assert len(losses) >= steps and np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    # The state starts in the layout the step returns it in: the step
    # compiled once.
    assert report["compiled_after_first_step"]["programs"] == 0, report
    assert report["granted_tpus"] == report["mesh_devices"] == n_devices, \
        report
    for name in ("embed", "tokens"):  # each device holds one n-th
        whole = np.prod(report[name]["global"])
        assert all(np.prod(part) * n_devices == whole
                   for part in report[name]["per_device"]), report[name]
    return report


def _post(conn, route, body):
    conn.request("POST", route, body=json.dumps(body),
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    data = resp.read()
    assert resp.status == 200, (resp.status, data[:500])
    if not body.get("stream"):
        return json.loads(data)["tokens"]
    assert resp.headers.get("Content-Type") == "text/event-stream"
    events = [line[len(b"data: "):] for line in data.split(b"\n\n")
              if line.startswith(b"data: ")]
    assert events[-1] == b"[DONE]", events[-3:]
    return [json.loads(e)["token"] for e in events[:-1]]


def serve_phase(cfg, *, max_batch_size, max_seq_len, prompt_lens,
                shared_head, max_tokens, compiles):
    """Deploy LLMDeployment under the default ray_config, put the HTTP
    proxy in front, and answer a handful of requests: prompts of
    `prompt_lens` (different prefill buckets) alternately streamed and
    unary, then a pair sharing a `shared_head`-token head, the second of
    it asked twice. Returns the report; raises if an answer is wrong."""
    import jax

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu._private.config import RayTpuConfig, ray_config
    from ray_tpu.models import init_params
    from ray_tpu.serve.llm import LLMDeployment

    assert dataclasses.asdict(ray_config) == \
        dataclasses.asdict(RayTpuConfig()), "ray_config is not the default"
    assert shared_head >= 4 * ray_config.llm_kv_block_tokens

    def params_fn():
        return jax.jit(functools.partial(init_params, cfg))(
            jax.random.PRNGKey(0))

    t_start = time.perf_counter()
    mark = compiles.mark()
    handle = serve.run(
        serve.deployment(LLMDeployment).bind(
            cfg, params_fn, max_batch_size=max_batch_size,
            max_seq_len=max_seq_len),
        route_prefix="/llm")
    proxy = serve.start_http_proxy()
    # The replica is constructing (compiling its programs); a call
    # through the handle returns when it can serve.
    stats = ray_tpu.get(handle.stats.remote())
    report = {
        "deploy_s": round(time.perf_counter() - t_start, 2),
        "warmup_s": round(stats["warmup_s"], 2),
        "compiled_programs": stats["compiled_programs"],
        "compiled": compiles.since(mark),
        "replica_health": {
            k: getattr(ray_config, f"serve_replica_health_{k}")
            for k in ("period_s", "timeout_s", "failures")},
    }

    rng = np.random.default_rng(1)

    def prompt(n):
        return rng.integers(0, cfg.vocab_size, n).tolist()

    head = prompt(shared_head)
    pair = [head + prompt(8), head + prompt(8)]
    mark = compiles.mark()
    t_start = time.perf_counter()
    conn = http.client.HTTPConnection(proxy.host, proxy.port, timeout=120)
    answers = [
        _post(conn, "/llm", {"prompt_ids": prompt(n),
                             "max_tokens": max_tokens, "stream": i % 2 == 0})
        for i, n in enumerate(prompt_lens)]
    answers += [_post(conn, "/llm", {"prompt_ids": p,
                                     "max_tokens": max_tokens,
                                     "stream": True}) for p in pair]
    again = _post(conn, "/llm", {"prompt_ids": pair[1],
                                 "max_tokens": max_tokens})
    conn.close()
    report["requests"] = len(answers) + 1
    report["tokens"] = sum(map(len, answers)) + len(again)
    report["requests_s"] = round(time.perf_counter() - t_start, 2)
    after = compiles.since(mark)
    report["compiled_after_warmup"] = after
    # An AOT executable that fell back to its jit function would show
    # as one of the engine's own programs compiling again.
    report["aot_fallbacks"] = sum(
        name in ("jit(_prefill_impl)", "jit(_decode_impl)",
                 "jit(_sample_admitted_impl)") for name in after["names"])
    stats = ray_tpu.get(handle.stats.remote())
    report["kv_cache"] = stats["kv_cache"]
    report["memory"] = _memory(jax.devices()[0])

    assert all(len(a) == max_tokens for a in answers + [again]), answers
    assert all(0 <= t < cfg.vocab_size for a in answers for t in a)
    assert again == answers[-1], (again, answers[-1])
    assert after["programs"] == 0 and report["aot_fallbacks"] == 0, after
    # The pair's second prompt found the first's head in the prefix
    # cache, and the repeat found all of it: the block copy programs ran.
    assert report["kv_cache"]["hits"] >= \
        2 * (shared_head // ray_config.llm_kv_block_tokens), report
    serve.shutdown()
    return report


def result_line(devices):
    """The last line of stdout, printed once every phase has passed: the
    device as JAX reports it. The driver's check reads exactly these
    keys."""
    return json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}})


def main():
    import jax
    import jaxlib

    import ray_tpu
    from ray_tpu._private.compile_cache import cache_dir
    from ray_tpu.models import LlamaConfig
    from ray_tpu.util.accelerators import require_tpu

    devices = require_tpu()
    faulthandler.dump_traceback_later(1150, exit=True)  # never hang a chip
    import libtpu
    versions = {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                "libtpu": libtpu.__version__}
    print(f"{len(devices)} x {devices[0].device_kind} "
          f"({devices[0].platform})", json.dumps(versions),
          "compile cache", cache_dir(), flush=True)

    compiles = CompileLog()
    cfg = dataclasses.replace(LlamaConfig.llama3_1b(), remat="gate")
    batch, seq = 4, 2048
    ray_tpu.init()

    t0 = time.perf_counter()
    kernels = check_flash_kernels(batch, seq, cfg.n_heads, cfg.n_kv_heads,
                                  cfg.head_dim)
    print(f"kernels ok in {time.perf_counter() - t0:.1f} s: flash forward, "
          f"dq and dk/dv within {_KERNEL_TOLERANCE:g} of the reference",
          flush=True)

    t0 = time.perf_counter()
    train = train_phase(cfg, batch=batch, seq=seq, steps=5,
                        n_devices=len(devices), compiles=compiles)
    train["wall_s"] = round(time.perf_counter() - t0, 2)
    # The compiled step holds the three Pallas kernels, each on this
    # device's share of the batch.
    calls = train["attention_calls"]
    assert set(calls) == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}, calls
    share = f"[{batch // len(devices)},{cfg.n_heads},{seq},{cfg.head_dim}]"
    assert all(call[0].endswith(share) for kernel in calls.values()
               for call in kernel), (calls, share)
    print(f"train ok in {train['wall_s']} s, of which compile "
          f"{train['compiled']['compile_s']} s (cache hits "
          f"{train['compiled']['cache_hits']}, misses "
          f"{train['compiled']['cache_misses']}): "
          f"{len(train['losses'])} steps on {train['mesh_devices']} "
          f"device(s), loss {train['losses'][0]} -> {train['losses'][-1]}, "
          f"{train['barrier']['step_s_host_fetch']} s a step to a host "
          f"fetch and {train['barrier']['step_s_block_until_ready']} s to "
          f"block_until_ready", flush=True)

    gc.collect()  # the train state leaves the chip before the model server
    t0 = time.perf_counter()
    serve = serve_phase(cfg, max_batch_size=8, max_seq_len=1024,
                        prompt_lens=(5, 40, 200, 700), shared_head=96,
                        max_tokens=8, compiles=compiles)
    serve["wall_s"] = round(time.perf_counter() - t0, 2)
    print(f"serve ok in {serve['wall_s']} s, of which deploy "
          f"{serve['deploy_s']} s (compile {serve['compiled']['compile_s']}"
          f" s summed over threads, cache hits "
          f"{serve['compiled']['cache_hits']}, misses "
          f"{serve['compiled']['cache_misses']}): "
          f"{serve['compiled_programs']} AOT programs, {serve['requests']} "
          f"requests and {serve['tokens']} tokens in {serve['requests_s']} "
          f"s, {serve['compiled_after_warmup']['programs']} compiles after "
          f"warm-up", flush=True)
    ray_tpu.shutdown()

    print("summary", json.dumps({
        "versions": versions, "compile_cache": cache_dir(),
        "kernels": kernels, "train": train, "serve": serve, "claim": None}))
    print(result_line(devices), flush=True)


if __name__ == "__main__":
    sys.exit(main())
