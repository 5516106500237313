"""Serve/LLM north-star benchmark: p50 TTFT + decode throughput.

Runs the continuous-batching engine (ray_tpu.serve.llm.LLMEngine) on the
local chip with Llama-3.2-1B-shaped random weights and measures, over a
set of concurrent streaming requests:

- TTFT: request arrival -> first streamed token (p50/p95), covering
  queueing + bucketed prefill (the BASELINE.json "Serve TTFT" north star
  the reference leaves unpublished).
- decode throughput: generated tokens/sec across the whole run.

Usage: python benchmarks/serve_bench.py [--requests 16] [--max-tokens 32]
Writes one JSON line to stdout.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

# Runnable from anywhere without PYTHONPATH.
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--requests", type=int, default=16)
    parser.add_argument("--max-tokens", type=int, default=32)
    parser.add_argument("--prompt-len", type=int, default=128)
    parser.add_argument("--batch-size", type=int, default=8)
    parser.add_argument("--decode-steps", type=int, default=8)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import LlamaConfig, init_params_sharded
    from ray_tpu.parallel import MeshConfig, create_mesh
    from ray_tpu.serve.llm import LLMEngine, SamplingParams
    from ray_tpu.util.accelerators import require_tpu

    require_tpu()
    cfg = LlamaConfig.llama3_1b()
    mesh = create_mesh(MeshConfig(data=-1))
    params = init_params_sharded(cfg, mesh, jax.random.PRNGKey(0))
    engine = LLMEngine(cfg, params, max_batch_size=args.batch_size,
                       max_seq_len=min(cfg.max_seq_len, 1024),
                       decode_steps=args.decode_steps)
    # Deploy-time AOT warmup (what LLMDeployment does): compiles every
    # prefill bucket + decode BEFORE traffic, off the request path. With
    # the persistent XLA compilation cache this is expensive only the
    # FIRST time a config is ever deployed on a machine.
    warmup_s = engine.warmup()
    engine.start()

    rng = np.random.default_rng(0)
    prompt_len = args.prompt_len
    prompts = [rng.integers(0, cfg.vocab_size, prompt_len).tolist()
               for _ in range(args.requests)]

    ttfts = []
    total_tokens = [0]
    first_times = []
    last_times = [0.0]
    lock = threading.Lock()

    def one_request(prompt):
        t0 = time.perf_counter()
        first = None
        count = 0
        for _tok in engine.generate(
                prompt, SamplingParams(max_tokens=args.max_tokens,
                                       temperature=0.0), stream=True):
            now = time.perf_counter()
            if first is None:
                first = now - t0
                with lock:
                    first_times.append(now)
            count += 1
            with lock:
                last_times[0] = max(last_times[0], now)
        with lock:
            ttfts.append(first)
            total_tokens[0] += count

    def run_wave(wave_prompts):
        """Run one wave; resets the accumulators on entry and returns a
        per-wave snapshot (no shared state to save/restore between
        waves)."""
        ttfts.clear()
        total_tokens[0] = 0
        first_times.clear()
        last_times[0] = 0.0
        t_start = time.perf_counter()
        threads = [threading.Thread(target=one_request, args=(p,))
                   for p in wave_prompts]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return {
            "wall": time.perf_counter() - t_start,
            "ttfts": sorted(ttfts),
            "tokens": total_tokens[0],
            "first_times": list(first_times),
            "last_time": last_times[0],
        }

    # Wave 2 is the steady-state serving number a loaded server sees;
    # wave-1 numbers ride along as cold-start.
    cold = run_wave(prompts)
    cold_p50 = cold["ttfts"][len(cold["ttfts"]) // 2]
    steady = run_wave(prompts)
    # Decode-rate wave: exactly batch_slots concurrent requests so the
    # post-first-token window is pure continuous-batching decode (a
    # multi-wave run interleaves wave N's decode with wave N+1's
    # prefills and would misattribute the time).
    dec_prompts = prompts[:args.batch_size]
    dec = run_wave(dec_prompts)
    decode_window = max(dec["last_time"] - max(dec["first_times"]), 1e-9)
    decode_tokens = dec["tokens"] - len(dec_prompts)
    decode_rate = round(decode_tokens / decode_window, 1)
    engine.stop()

    wall = steady["wall"]
    cold_wall = cold["wall"]
    total_tokens[0] = steady["tokens"]
    sorted_ttfts = steady["ttfts"]
    p50 = sorted_ttfts[len(sorted_ttfts) // 2]
    p95 = sorted_ttfts[min(len(sorted_ttfts) - 1,
                           int(len(sorted_ttfts) * 0.95))]
    print(json.dumps({
        "metric": "serve_ttft_p50_ms",
        "value": round(p50 * 1e3, 1),
        "unit": "ms",
        "detail": {
            "config": "llama-1.24B",
            "ttft_p95_ms": round(p95 * 1e3, 1),
            "cold_start_ttft_p50_ms": round(cold_p50 * 1e3, 1),
            "cold_start_wall_s": round(cold_wall, 2),
            "deploy_warmup_s": round(warmup_s, 2),
            "decode_tokens_per_s": decode_rate,
            "end_to_end_tokens_per_s": round(total_tokens[0] / wall, 1),
            "requests": args.requests,
            "prompt_len": prompt_len,
            "max_tokens": args.max_tokens,
            "batch_slots": args.batch_size,
            "decode_steps": args.decode_steps,
        },
    }))


if __name__ == "__main__":
    main()
