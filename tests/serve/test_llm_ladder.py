"""The engine's prefill ladder and a deployment's teardown, in the quick
tier (`tests/serve/test_llm.py` is the slow one)."""

import jax
import pytest

from ray_tpu.models.llama import LlamaConfig, init_params
from ray_tpu.serve import llm
from ray_tpu.serve.llm import LLMDeployment


@pytest.mark.parametrize("n_tokens, bucket", [
    (1, 1), (2, 2), (3, 4), (129, 256), (856, 1024), (2049, 4096),
    (4096, 4096), (4097, 5120), (5120, 5120), (6144, 6144), (8193, 9216),
    (11202, 11264), (16383, 16384)])
def test_prefill_bucket(n_tokens, bucket):
    """Powers of two to 4,096, multiples of 1,024 past it: a long
    prompt pads by less than 1,024 tokens."""
    assert llm.prefill_bucket(n_tokens) == bucket
    assert bucket >= n_tokens
    assert llm.prefill_bucket(bucket) == bucket


@pytest.mark.parametrize("limit, max_seq, ladder", [
    (1, 128, [1]),
    (16, 128, [1, 2, 4, 8, 16]),
    (100, 128, [1, 2, 4, 8, 16, 32, 64, 128]),
    (856, 1024, [2 ** i for i in range(11)]),
    (900, 1000, [2 ** i for i in range(10)] + [1000]),
    (11202, 16384, [2 ** i for i in range(13)]
     + [5120, 6144, 7168, 8192, 9216, 10240, 11264]),
    (16384, 16384, [2 ** i for i in range(13)]
     + list(range(5120, 16385, 1024))),
    (6000, 5000, [2 ** i for i in range(13)] + [5000])])
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
