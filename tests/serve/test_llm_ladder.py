"""The engine's prefill ladder and a deployment's teardown, in the quick
tier (`tests/serve/test_llm.py` is the slow one)."""

import jax
import pytest

from ray_tpu.models.llama import LlamaConfig, init_params
from ray_tpu.serve import llm
from ray_tpu.serve.llm import LLMDeployment


@pytest.mark.parametrize("n_tokens, bucket", [
    (1, 1), (2, 2), (3, 4), (129, 256), (256, 256), (257, 384), (380, 384),
    (385, 512), (513, 768), (769, 1024), (856, 1024), (1025, 1536),
    (1537, 2048), (2049, 3072), (3073, 4096), (3452, 4096), (4096, 4096),
    (4097, 5120), (5120, 5120), (6144, 6144), (8193, 9216), (11202, 11264),
    (16383, 16384)])
def test_prefill_bucket(n_tokens, bucket):
    """Powers of two to 256, the next quarter of the prompt's power of
    two to 2,048 (by 128, 256 and 512), multiples of 1,024 past it."""
    assert llm.prefill_bucket(n_tokens) == bucket
    assert bucket >= n_tokens
    assert llm.prefill_bucket(bucket) == bucket


def test_the_rule_of_the_buckets_for_every_length():
    """Every prompt up to 16,384 tokens: its bucket holds it, is its own
    bucket, is a multiple of 128 past 128 (every tile on the prefill
    path divides it), pads by under a step of 128 or a third of itself,
    and is a power of two up to 256 and a multiple of 1,024 past 4,096,
    as before the quarters came."""
    for n in range(1, 16385):
        bucket = llm.prefill_bucket(n)
        assert n <= bucket == llm.prefill_bucket(bucket), n
        assert bucket <= 128 or bucket % 128 == 0, n
        assert bucket - n < max(128, bucket / 3), n
        if n <= 256:
            assert bucket == 1 << (n - 1).bit_length(), n
        if n > 4096:
            assert bucket == -(-n // 1024) * 1024, n


_TO_256 = [2 ** i for i in range(9)]
_TO_1024 = _TO_256 + [384, 512, 768, 1024]
_TO_4096 = _TO_1024 + [1536, 2048, 3072, 4096]


@pytest.mark.parametrize("limit, max_seq, ladder", [
    (1, 128, [1]),
    (16, 128, [1, 2, 4, 8, 16]),
    (100, 128, [1, 2, 4, 8, 16, 32, 64, 128]),
    (380, 1024, _TO_256 + [384]),
    (700, 1024, _TO_1024[:-1]),
    (856, 1024, _TO_1024),
    (900, 1000, _TO_1024[:-1] + [1000]),
    (1234, 2048, _TO_1024 + [1536]),
    (11202, 16384, _TO_4096
     + [5120, 6144, 7168, 8192, 9216, 10240, 11264]),
    (16384, 16384, _TO_4096 + list(range(5120, 16385, 1024))),
    (6000, 5000, _TO_4096 + [5000])])
def test_bucket_ladder(limit, max_seq, ladder):
    """What `warmup` compiles: every bucket a prompt of up to `limit`
    tokens can take, so that `_serve_bucket` finds each compiled."""
    assert llm.bucket_ladder(limit, max_seq) == ladder
    for n in {1, limit // 3 + 1, limit // 2 + 1, min(limit, max_seq)}:
        assert min(llm.prefill_bucket(n), max_seq) in ladder


def test_a_stopped_deployment_stops_its_engine():
    """`LLMDeployment.__del__` (what a stopped replica calls) ends the
    engine's loop: no thread of it is left inside a device call when
    the interpreter exits."""
    cfg = LlamaConfig.debug()
    params = init_params(cfg, jax.random.PRNGKey(0))
    dep = LLMDeployment(cfg, lambda: params, max_batch_size=2,
                        max_seq_len=32, warmup=False)
    assert dep({"prompt_ids": [1, 2, 3], "max_tokens": 2})["tokens"]
    thread = dep.engine._thread
    assert thread.is_alive()
    dep.__del__()
    assert not thread.is_alive()


def test_a_deployment_of_a_model_with_a_state_leaf_has_no_prefix_cache():
    """Nemotron-H's toy through `LLMDeployment`: the same wrapper and
    engine as any other model, but no prefix cache is built for a cache
    with state leaves, so the router gets no digests, and no read-back
    program is compiled by the warm-up."""
    from ray_tpu.models import nemotron_h

    cfg = nemotron_h.NemotronHConfig.debug_nemotron()
    params = nemotron_h.init_params(cfg, jax.random.PRNGKey(0))
    dep = LLMDeployment(cfg, lambda: params, max_batch_size=2,
                        max_seq_len=32, warmup_max_prompt_len=8)
    try:
        assert dep.engine.prefix_cache is None
        assert dep.prefix_digests() is None
        ladder = llm.bucket_ladder(8, 32)
        assert sorted(dep.engine._prefill_exec) == ladder
        # The ladder, decode and the sampler: no read-back's gather.
        assert dep.stats()["compiled_programs"] == len(ladder) + 2
        first = dep({"prompt_ids": [5, 6, 7, 8, 9], "max_tokens": 4})
        again = dep({"prompt_ids": [5, 6, 7, 8, 9], "max_tokens": 4})
        assert first["tokens"] == again["tokens"] and len(first["tokens"]) == 4
        stats = dep.stats()
        assert "kv_cache" not in stats
        assert stats["totals"]["experts_held_steps"] > 0
    finally:
        dep.__del__()


def test_a_llama_engine_still_builds_its_prefix_cache():
    cfg = LlamaConfig.debug()
    engine = llm.LLMEngine(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                           max_batch_size=2, max_seq_len=64)
    assert engine.prefix_cache is not None
    assert engine._is_state == [False, False]
    assert len(engine._leaves) == 2


def test_an_llm_deployment_names_its_own_in_flight_cap():
    """Requests beyond the slots queue in the engine, by priority: the
    generic cap of 100 queries a replica would hold them at the router
    instead, where the proxy sheds them after its queue timeout."""
    from ray_tpu import serve

    assert serve.deployment(LLMDeployment).max_concurrent_queries == 1024
    assert serve.deployment(
        LLMDeployment, max_concurrent_queries=7).max_concurrent_queries == 7

    @serve.deployment
    class Plain:
        pass

    assert Plain.max_concurrent_queries == 100


def test_a_queued_stream_beats_while_the_engine_decodes_for_others(
        monkeypatch):
    """One slot, two streamed requests through the deployment: the
    second waits in the engine's queue for the first to end, and its
    stream says so (`STREAM_WAITING_KEY` chunks, which a stream's
    reader swallows and starts its timeout anew for) until its own
    tokens come, the same tokens as without the wait."""
    from ray_tpu.serve.streaming import STREAM_WAITING_KEY

    monkeypatch.setattr(llm, "WAITING_BEAT_S", 0.005)
    cfg = LlamaConfig.debug()
    params = init_params(cfg, jax.random.PRNGKey(0))
    dep = LLMDeployment(cfg, lambda: params, max_batch_size=1,
                        max_seq_len=256, warmup_max_prompt_len=8)
    try:
        # (On a loaded machine even these two may beat: the loop counts
        # its first block's steps before it delivers the first token.)
        alone = [c["token"] for c in dep(
            {"prompt_ids": [7, 8, 9], "max_tokens": 4, "stream": True})
            if STREAM_WAITING_KEY not in c]
        first = (c for c in dep({"prompt_ids": [5, 6], "max_tokens": 200,
                                 "stream": True})
                 if STREAM_WAITING_KEY not in c)
        assert next(first)["index"] == 0  # it holds the slot
        second = list(dep({"prompt_ids": [7, 8, 9], "max_tokens": 4,
                           "stream": True}))
        assert len(list(first)) == 199
        beats = [c for c in second if STREAM_WAITING_KEY in c]
        tokens = [c for c in second if STREAM_WAITING_KEY not in c]
        assert beats and second[:len(beats)] == beats
        assert [c["token"] for c in tokens] == alone
        assert [c["index"] for c in tokens] == [0, 1, 2, 3]
    finally:
        dep.__del__()


def test_a_queued_stream_of_a_loop_that_does_not_step_gives_no_beat():
    """The beat means the engine works for others. A request in the
    queue of a loop that runs no decode step is silent, and the
    reader's timeout ends it as before."""
    import threading

    cfg = LlamaConfig.debug()
    engine = llm.LLMEngine(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                           max_batch_size=1, max_seq_len=32)
    engine.start = lambda: None  # the loop never runs
    stream = engine.generate([5, 6], llm.SamplingParams(max_tokens=2),
                             stream=True, beat_s=0.005)
    seen = []
    reader = threading.Thread(target=lambda: seen.extend(stream),
                              daemon=True)
    reader.start()
    reader.join(timeout=0.2)
    assert reader.is_alive() and seen == []
    engine._queue.get_nowait().out_queue.put(None)  # let the reader go
    reader.join(timeout=5)
    assert not reader.is_alive() and seen == []
