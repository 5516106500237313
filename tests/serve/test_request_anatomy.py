"""Request anatomy (PR 18): end-to-end critical-path attribution on the
real LLM serving path, plus the affinity hit/miss counters.

The flagship demo: a cache-MISS request through the proxy → replica →
LLM engine names ``llm.prefill`` as its dominant stage; the cache-HIT
request that follows (same shared prompt head, served from the prefix
cache) does not. A prefill-weighted LLM subclass makes the anatomy
deterministic on CPU — sleeping proportionally to the tokens actually
prefilled is exactly what a real transformer's prefill cost does.

Kept tier-1-sized: one tiny 1-layer model, two requests, one proxy.
"""

import http.client
import json
import time
import urllib.request

import pytest

import jax
import jax.numpy as jnp

import ray_tpu
from ray_tpu import serve
from ray_tpu._private import critical_path, perf_stats
from ray_tpu._private.config import ray_config
from ray_tpu.models.llama import LlamaConfig, init_params
from ray_tpu.serve import llm as llm_mod
from ray_tpu.serve.llm import LLMDeployment, LLMEngine

_TINY = LlamaConfig(vocab_size=64, dim=16, n_layers=1, n_heads=2,
                    n_kv_heads=2, hidden_dim=32, max_seq_len=32,
                    dtype=jnp.float32, remat=False)


class _PrefillWeightedEngine(LLMEngine):
    """LLMEngine with a model-realistic cost profile on CPU: prefill
    pays per token actually prefilled (so a prefix-cache hit skips
    most of it), decode pays a fixed per-step cost."""

    def _run_prefill(self, tokens, slot, length, start, bucket):
        time.sleep(0.025 * int(length))
        return super()._run_prefill(tokens, slot, length, start, bucket)

    def _run_decode(self, last, lengths, temps, topks):
        time.sleep(0.03)
        return super()._run_decode(last, lengths, temps, topks)


@pytest.fixture
def llm_up(monkeypatch):
    # Replicas run in-process under the local backend, so patching the
    # module's engine class reshapes every replica this test deploys.
    monkeypatch.setattr(llm_mod, "LLMEngine", _PrefillWeightedEngine)
    monkeypatch.setattr(ray_config, "llm_prefix_cache", True)
    monkeypatch.setattr(ray_config, "llm_kv_block_tokens", 4)
    monkeypatch.setattr(ray_config, "llm_prefix_shm_tier", False)
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4)
    yield
    serve.shutdown()
    ray_tpu.shutdown()


def _sse_drain(resp):
    n = 0
    buf = b""
    while True:
        chunk = resp.read1(65536)
        if not chunk:
            break
        buf += chunk
        done = False
        while b"\n\n" in buf:
            line, buf = buf.split(b"\n\n", 1)
            if not line.startswith(b"data: "):
                continue
            if line[len(b"data: "):] == b"[DONE]":
                done = True
                break
            n += 1
        if done:
            break
    return n


def _stage_sum(entry, stage):
    return sum(s["dur_s"] for s in entry["stages"]
               if s["stage"] == stage)


def test_cache_miss_names_prefill_dominant_and_traces_chain(llm_up):
    """The attribution demo + the /api/traces span-chain contract in
    one serve session (model warmup is the expensive part)."""
    params = init_params(_TINY, jax.random.PRNGKey(0))
    serve.run(
        serve.deployment(LLMDeployment).bind(
            _TINY, lambda: params, max_batch_size=2, max_seq_len=32,
            warmup_max_prompt_len=16),
        route_prefix="/llm")
    proxy = serve.start_http_proxy()

    shared = list(range(1, 13))  # 12 tokens = 3 full 4-token blocks
    conn = http.client.HTTPConnection(proxy.host, proxy.port,
                                      timeout=60)
    # Absorb replica warm-up with a throwaway request (disjoint 2-token
    # prompt: no shared-prefix blocks enter the cache). Without it the
    # first timed request queues behind warm-up and — correctly! —
    # attributes those seconds to sched.queue instead of prefill.
    conn.request("POST", "/llm",
                 body=json.dumps({"prompt_ids": [40, 41],
                                  "max_tokens": 1, "stream": True}),
                 headers={"Content-Type": "application/json"})
    warm = conn.getresponse()
    assert warm.status == 200
    _sse_drain(warm)
    warm.read()
    for trace_id, tail in (("anatomy-miss", [20, 21]),
                           ("anatomy-hit", [30, 31])):
        conn.request(
            "POST", "/llm",
            body=json.dumps({"prompt_ids": shared + tail,
                             "max_tokens": 4, "stream": True}),
            headers={"Content-Type": "application/json",
                     "X-Trace-Id": trace_id})
        resp = conn.getresponse()
        assert resp.status == 200
        assert _sse_drain(resp) == 4
        resp.read()
    conn.close()

    # The proxy's request envelope closes the waterfall moments after
    # the client drains the stream; poll briefly for both.
    deadline = time.monotonic() + 10
    wf = {}
    while time.monotonic() < deadline:
        wf = {e["trace_id"]: e
              for e in critical_path.finished_waterfalls()}
        if {"anatomy-miss", "anatomy-hit"} <= set(wf):
            break
        time.sleep(0.05)
    assert {"anatomy-miss", "anatomy-hit"} <= set(wf), list(wf)
    miss, hit = wf["anatomy-miss"], wf["anatomy-hit"]

    # The demo: the cold request's time went to prefill; the
    # prefix-cache hit skipped the shared head, so prefill no longer
    # dominates it.
    assert miss["dominant_stage"] == "llm.prefill", miss
    assert hit["dominant_stage"] != "llm.prefill", hit
    assert _stage_sum(hit, "llm.prefill") < \
        _stage_sum(miss, "llm.prefill")

    # The attribution vector reached the fast-path metric under the
    # route tag (what ray_tpu_request_stage_seconds{route,stage}
    # exports).
    vecs = critical_path.attribution_vectors()
    assert vecs["/llm"]["llm.prefill"]["count"] >= 2
    assert vecs["/llm"]["llm.decode"]["count"] >= 2

    # /api/traces: the proxy→replica→prefill chain shares ONE traceId
    # (the supplied one), task spans and synthetic stage spans alike —
    # the TTFT-end-to-end stitching the ISSUE names.
    from ray_tpu.dashboard import shutdown_dashboard, start_dashboard

    server = start_dashboard(port=0)
    try:
        base = f"http://{server.host}:{server.port}"
        with urllib.request.urlopen(f"{base}/api/traces",
                                    timeout=10) as resp:
            envelope = json.loads(resp.read())
        spans = envelope["resourceSpans"][0]["scopeSpans"][0]["spans"]
        mine = [s for s in spans if s["traceId"] == "anatomy-miss"]
        names = {s["name"] for s in mine}
        assert {"stage.proxy.dispatch", "stage.replica.execute",
                "stage.llm.prefill"} <= names, sorted(names)
        # At least one REAL task span (the replica call) rides the
        # same trace id as the synthetic stage spans.
        assert any(not s["spanId"].startswith("stage:")
                   for s in mine), mine
    finally:
        shutdown_dashboard()


class _FakeReplica:
    def __init__(self, name):
        self._actor_name = name


def test_affinity_hit_miss_counters():
    """ReplicaDirectTable.acquire increments serve_affinity_hits when
    an affinity-scored request lands on its best cache-affine replica,
    serve_affinity_misses when it spills or finds no capacity."""
    from ray_tpu._private.kv_cache import chain_keys
    from ray_tpu.serve._private.membership import ReplicaDirectTable

    table = ReplicaDirectTable(cap=1)
    a, b = _FakeReplica("a"), _FakeReplica("b")
    assert table.update(1, [a, b])
    tokens = list(range(8))  # 2 full 4-token blocks
    table.set_digests({"a": {
        "seed": "s", "block_tokens": 4, "block_bytes": 64,
        "keys": list(chain_keys(tokens, 4, "s"))}})

    def counts():
        return (perf_stats.counter("serve_affinity_hits").value,
                perf_stats.counter("serve_affinity_misses").value)

    h0, m0 = counts()
    # Best-scored replica has capacity: a hit.
    tok = table.acquire(affinity_tokens=tokens)
    assert tok is not None and tok.replica is a
    assert counts() == (h0 + 1, m0)
    # Best at cap: the claim spills to the unaffine replica — a miss.
    tok2 = table.acquire(affinity_tokens=tokens)
    assert tok2 is not None and tok2.replica is b
    assert counts() == (h0 + 1, m0 + 1)
    # Everyone at cap: no token, still a miss the hit-rate panel sees.
    assert table.acquire(affinity_tokens=tokens) is None
    assert counts() == (h0 + 1, m0 + 2)
    # No affinity hint: neither counter moves (round-robin contract).
    table.release(tok)
    assert table.acquire() is not None
    assert counts() == (h0 + 1, m0 + 2)
