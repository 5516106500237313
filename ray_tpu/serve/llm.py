"""Continuous-batching LLM engine for TPU serving.

No reference equivalent (the reference serves arbitrary Python callables);
this is the TPU-specific serving layer SURVEY.md §7 step 8 calls for:
compiled-XLA replicas with continuous batching. Design constraints come
from XLA's compilation model — every device program must have static
shapes — so:

- The KV cache is slot-based: `max_batch_size` sequence slots, each with a
  `max_seq_len` KV region. Admission = prefill into a free slot;
  retirement frees the slot. The decode step is ONE fixed-shape jit
  program over all slots regardless of occupancy.
- The engine knows no model. `models.serving.served_model` names, by the
  config's type, the cached forward pass and the cache's initialiser;
  the cache is whatever pytree that gives, every leaf
  [layers_i, slots, ...]: row leaves, [layers_i, slots, max_seq, ...]
  (Llama's K and V; a latent-attention model's latents, rotary keys and
  indexer keys, stacked by runs of like layers), and state leaves with
  no sequence axis (a state-space layer's recurrent state), which the
  model says apart (`ServedModel.state_leaves`). Everything here that
  touches it works leaf by leaf: a slot's slice on every leaf, the
  prefix cache's reads, writes and payloads on the row leaves alone. A
  model with a state leaf is served with no prefix cache
  (`models/serving.py` says why).
- Prefill lengths are bucketed (`prefill_bucket`): to powers of two up
  to 256 tokens, to the next quarter of the prompt's power of two past
  that (384, 512, 768, 1,024, 1,536, 2,048) and to multiples of 1,024
  from 2,049 on, so some 8 + 2 log2(max_seq / 256) prefill programs
  compile up to 2,048 tokens and max_seq / 1024 past it, and a prompt
  pays for padding of under a third of its bucket and at most 1,023
  tokens, not for as many again as it has.
- Sampling (greedy / temperature / top-k) runs on device; one token per
  slot per step streams back to waiting callers.
- That is the one-token contract: a prefill yields a request's first
  token, a decode step one more a slot, the host's lengths grow by one
  a token. A model that generates by blocks (`ServedModel.block_length`
  B; diffusion over blocks) is served by the same loop, cache,
  admission and spans through `_BlockEngine`, whose step is another:
  `forward` sees a slot's block of B positions, which see each other
  and the slot's rows before them; a step's forward either *denoises* a
  block (some of its open positions take their tokens) or *commits* it
  (its rows stay, the length grows by B, the next block opens); a step
  returns, a slot, a row of B tokens, the steps they were fixed at and
  whether the block is whole now, so 0 or B tokens and not one; a
  prefill covers the prompt's whole blocks and yields no token; the
  host counts lengths by blocks, tokens where they are handed to a
  request, and the forwards of each phase (`slot_forwards_denoise`,
  `slot_forwards_commit`, `tokens_fixed`, `blocks_emitted`).

The engine is thread-safe: callers enqueue requests and block on their
completion; a background loop interleaves admission and decode — the
continuous-batching scheduler (admission between decode steps, no
generation stall).

Prefix/KV cache (PR 16): full ``llm_kv_block_tokens``-sized chunks of
every admitted prompt are hash-chained into the
:class:`~ray_tpu._private.kv_cache.PrefixCache` decision core, with the
block KV payloads read back off-device into a host store (one
asynchronous gather a request, finished on the host while a decode
block runs: "prefix/KV cache" below). A later
request sharing the prompt head copies the matched blocks straight into
its slot's KV region and prefills ONLY the tail at the tail's bucket —
the shared-head prefill compute (the dominant pre-first-token cost on a
chatbot workload) is skipped entirely. Evicted-but-warm blocks persist
as shm-plane objects (spill-backed, tenant-charged), so a hit on
another replica restores KV bytes via the object plane instead of
recomputing. Chain keys are seeded with the model identity, so
multi-model replicas can never cross-hit.

Multi-model multiplexing: a replica holds N weight variants
(``LLMDeployment(models={...})``); the compiled programs take params as
ARGUMENTS, so a swap is one ``device_put`` — no recompile. Requests
carry a model tag and a priority class; interactive outranks batch at
the slot shed point.
"""

from __future__ import annotations

import collections
import dataclasses
import hashlib
import itertools
import queue
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ray_tpu._private import critical_path
from ray_tpu._private.compile_cache import enable_persistent_cache
from ray_tpu._private import perf_stats
from ray_tpu._private.config import ray_config
from ray_tpu._private.kv_cache import PrefixCache, chain_keys
from ray_tpu.models.serving import served_model
from ray_tpu.ops import stacked_product
from ray_tpu.serve.streaming import (LAG_SAMPLE_EVERY, STREAM_WAITING_KEY,
                                     WAITING_BEAT_S)


# A prompt's prefill bucket. Padding rows cost what real rows cost (a
# prefill is compute bound and a line through its rows), and a compiled
# program a bucket costs set-up time and memory, so a bucket stands a
# fixed share of its octave above the last:
# - `_BUCKET_STEPS` steps to a power of two (two buckets an octave): a
#   prompt is padded by under a third of its bucket and a sixth at the
#   mean, where a power of two's padding is under a half and three
#   tenths, at one more program an octave. Eight steps take two thirds
#   of the padding away and not half of it, but a program more costs a
#   family whose prefill holds many kernel call sites 2 to 3 s of every
#   set-up, cached or not: at eight, four more programs put LFM2's warm
#   set-up 11 % up (PERF.md section 6, PR 56).
# - no step under `_BUCKET_STEP_MIN`: every tile on the prefill path is
#   a multiple of it (a row of lanes, the flash kernels' least tile,
#   Nemotron's 128-token chunk, twice Olmo-Hybrid's), so up to it the
#   buckets are the powers of two.
# - none over `_BUCKET_STEP_MAX`: a prefill's cost grows faster than
#   its length (attention), so past 4,096 tokens a quarter of a power
#   of two would cost seconds of padding.
_BUCKET_STEPS = 4
_BUCKET_STEP_MIN = 128
_BUCKET_STEP_MAX = 1024


def prefill_bucket(n_tokens: int) -> int:
    """The smallest prefill bucket that holds `n_tokens`: the next power
    of two up to `_BUCKET_STEP_MIN`; past it `n_tokens` rounded up to
    the step of its octave, which is the `_BUCKET_STEPS`-th part of the
    next power of two, held between `_BUCKET_STEP_MIN` and
    `_BUCKET_STEP_MAX` (so 129-256 take 256, 257-512 take 384 or 512,
    513-1,024 take 768 or 1,024, and from 2,049 on every bucket is a
    multiple of 1,024)."""
    octave = 1 << max(n_tokens - 1, 0).bit_length()
    if octave <= _BUCKET_STEP_MIN:
        return octave
    step = min(max(octave // _BUCKET_STEPS, _BUCKET_STEP_MIN),
               _BUCKET_STEP_MAX)
    return -(-n_tokens // step) * step


def bucket_ladder(limit: int, max_seq: int) -> List[int]:
    """Every prefill bucket up to the one that holds `limit` tokens,
    none over a slot's `max_seq`."""
    top = min(prefill_bucket(limit), max_seq)
    buckets, b = [], 1
    while b < top:
        buckets.append(b)
        b = prefill_bucket(b + 1)
    return buckets + [top]


class PromptTooLongError(ValueError):
    """Prompt exceeds the engine's slot KV region (``max_seq_len - 1``
    tokens: one position must remain for generation). Raised at
    ``generate()`` — the old behavior silently truncated the head,
    which corrupts answers instead of failing loudly."""

    def __init__(self, n_tokens: int, cap: int):
        super().__init__(
            f"prompt of {n_tokens} tokens exceeds the engine's "
            f"{cap}-token cap (max_seq_len {cap + 1}); truncate or "
            f"shard client-side")
        self.n_tokens = n_tokens
        self.cap = cap


class UnknownModelError(ValueError):
    """X-Model names a variant this deployment does not hold."""

    def __init__(self, model: str, known):
        super().__init__(
            f"unknown model {model!r}; this replica serves {known}")
        self.model = model
        self.known = list(known)


class ModelSwapDeadlineError(RuntimeError):
    """A cold-start weight swap blew the ``llm_model_swap_deadline_s``
    SLA. The loaded weights STAY cached (and published to the shm
    plane), so an immediate retry is warm — the deadline is a latency
    contract, not a capability failure."""

    def __init__(self, model: str, took_s: float, deadline_s: float):
        super().__init__(
            f"swap to model {model!r} took {took_s:.2f}s, over the "
            f"{deadline_s:.2f}s cold-start deadline (retry is warm)")
        self.model = model
        self.took_s = took_s
        self.deadline_s = deadline_s


# lax.top_k needs a static k: per-slot top_k values are clamped to this.
_TOP_K_MAX = 64

# A read-back's gather hands its rows over in arrays of at most this
# many bytes: the TPU runtime copies an array of 32 MiB to the host in
# 35 ms and one of 28 MiB in 5 (v5e, jax 0.9.0; PERF.md section 6, PR 28).
_D2H_ARRAY_BYTES = 16 << 20

# A pass of the loop whose host time reaches this leaves a stall record
# under the name of the tile that held most of it (`_end_pass`). Set
# from the longest healthy pass of each serve cell, to stand at least
# twice over the longest of them, 187 ms: a pass that a hold of the
# interpreter fell into, the cyclic collector's gen-2 pass for one
# (v5e; PERF.md section 6, PR 53; ISSUE 53's 0.25 s stood under twice
# that).
_STALL_S = 0.5
_STALL_NAMES = {
    "engine.prefix_admit": "engine.loop_stall.prefix_admit",
    "engine.admit_wave": "engine.loop_stall.admit_wave",
    "engine.decode_dispatch": "engine.loop_stall.decode_dispatch",
    "engine.consume_block": "engine.loop_stall.consume_block",
    "engine.first_tokens": "engine.loop_stall.first_tokens",
    "other": "engine.loop_stall.other",
}
# `engine.prefix_admit`'s attributes: the rise across the span of these
# totals (`forced` counts every read-back waited for inside it, be it
# for an evicted block's bytes or for the cap on pending ones).
_PREFIX_ADMIT = {
    "created": "kv_blocks_read_back", "evicted": "kv_blocks_evicted",
    "offloaded": "kv_blocks_offloaded", "forced": "kv_readbacks_forced",
    "backpressure_waits": "kv_offload_backpressure_waits",
    "put_us": "kv_offload_us",
}
# The process's count of creates that found the shm arena full
# (`shm_plane.maybe_put`).
_SHM_BACKPRESSURE_WAITS = perf_stats.counter(
    "object_create_backpressure_waits")


@dataclasses.dataclass
class SamplingParams:
    max_tokens: int = 64
    temperature: float = 0.0  # 0 → greedy
    top_k: int = 0            # 0 = full softmax; clamped to _TOP_K_MAX
    stop_token_ids: tuple = ()
    # Of a model that generates by blocks: in how many denoising steps
    # a block's positions are fixed (it divides the block's length);
    # None: the model config's own.
    denoising_steps: Optional[int] = None


@dataclasses.dataclass
class _Request:
    request_id: int
    prompt: List[int]
    params: SamplingParams
    out_queue: "queue.Queue"
    tokens: List[int] = dataclasses.field(default_factory=list)
    slot: int = -1
    t_arrival: float = 0.0
    t_first_token: Optional[float] = None
    model: Optional[str] = None
    priority: int = 1     # 0 interactive > 1 normal > 2 batch
    job: str = "default"
    # Critical-path attribution: the HTTP request's trace id (stamped
    # at generate() from the calling task's ambient trace, "" outside
    # any trace) plus the per-request stage marks the engine loop sets
    # while the request crosses admit → kv-lookup → prefill → sample.
    trace_id: str = ""
    t_kv_done: float = 0.0
    # When the loop put the newest token whose count is a multiple of
    # `LAG_SAMPLE_EVERY` into `out_queue`: its reader records
    # `stream.wake` from it.
    t_put: float = 0.0
    # Of a model that generates by blocks: the prompt tokens that open
    # the request's first block (known positions, not part of the
    # answer), the blocks handed over so far and when the last was.
    known: int = 0
    blocks: int = 0
    t_block: float = 0.0


@dataclasses.dataclass
class _Readback:
    """One admitted request's newly created blocks on their way to the
    host: the slot's rows [start, start + rows) as device arrays whose
    copy to the host has been started (`_read_rows_impl`'s parts, leaf
    after leaf of the cache), and the handles (by block id) whose
    payload is still wanted."""
    arrays: tuple
    start: int
    rows: int
    handles: Dict[int, Any]
    nbytes: int


class LLMEngine:
    def __new__(cls, cfg=None, *args, **kwargs):
        # A model that generates by blocks has a step, an admission and
        # a hand-over of its own (`_BlockEngine`); the loop is this one.
        if cls is LLMEngine and cfg is not None \
                and served_model(cfg).block_length:
            cls = _BlockEngine
        return super().__new__(cls)

    def __init__(self, cfg, params, *,
                 max_batch_size: int = 8, max_seq_len: Optional[int] = None,
                 decode_steps: int = 1, seed: int = 0,
                 model: str = "default"):
        # Before the first compile of the process (the cache zeros
        # below): re-deploys load executables instead of recompiling.
        enable_persistent_cache()
        self.cfg = cfg
        self.params = params
        self.model = model
        self.n_slots = max_batch_size
        # Tokens generated per decode dispatch (in-program scan).
        # >1 trades admission granularity (a new request waits for the
        # current block) for K-fold fewer dispatches.
        self.decode_steps = max(1, int(decode_steps))
        self.max_seq = max_seq_len or cfg.max_seq_len
        self._served = served_model(cfg)
        # Positions a slot is fed a step: a token, or a block.
        self._step_len = self._served.block_length or 1
        self.cache = self._served.init_cache(cfg, self.n_slots,
                                             self.max_seq)
        # The cache's row leaves as shapes, [layers_i, slots, max_seq,
        # ...]: what the host needs of them (the arrays themselves are
        # donated to every program that writes them). `_is_state` says,
        # in the leaves' order, which of the cache's leaves are not
        # among them: state, with no sequence axis.
        self._is_state = jax.tree.leaves(
            self._served.state_leaves(self.cache))
        self._leaves = [jax.ShapeDtypeStruct(x.shape, x.dtype)
                        for x, state in zip(jax.tree.leaves(self.cache),
                                            self._is_state) if not state]
        self._rng = jax.random.PRNGKey(seed)

        # Per-slot host state.
        self._free_slots = list(range(self.n_slots))
        self._slot_req: Dict[int, _Request] = {}
        self._lengths = np.zeros(self.n_slots, np.int32)  # tokens in cache
        self._active = np.zeros(self.n_slots, bool)

        self._queue: "queue.Queue[_Request]" = queue.Queue()
        self._req_counter = itertools.count()
        self._lock = threading.Lock()
        self._running = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # Pipelined decode: the block in flight as (its device token
        # array, the slots' requests when it was dispatched, what the
        # model counted, whether prefills were dispatched in front of
        # it); its host
        # fetch happens while the next block computes. The decode
        # carries (each slot's last token and length) live on the
        # device: a block hands them to the next, and an admission
        # wave's sample program writes the admitted slots' first tokens
        # and prompt lengths into them, so that no wave waits for a
        # result. `_first_tokens` is the last wave's (sample output,
        # its copy to the host started; the admitted (request, slot)
        # pairs, a row each) until the loop delivers it, behind the
        # next decode dispatch.
        self._pending_block = None
        self._first_tokens = None
        # A wave has dispatched prefills since the last decode dispatch
        # (the next block runs behind them), and when the last block's
        # hand-over ended (None: no block was in flight before the one
        # that is now): the two ends of `engine.block_gap.*`.
        self._wave_dispatched = False
        self._handed_over: Optional[float] = None
        # Running totals of what the loop's spans count at the same
        # seams (`metrics()["totals"]`); only the loop's thread adds
        # (and, to `kv_readbacks_forced`, whoever forces a read-back).
        self._totals = dict.fromkeys((
            "decode_steps", "active_slot_steps", "tokens_kept",
            "tokens_discarded", "prefill_tokens_real",
            "prefill_tokens_bucketed", "admit_waves",
            "admit_waves_behind_block", "slot_steps_stale", "admissions",
            "kv_blocks_read_back", "kv_bytes_read_back",
            "kv_readbacks_deferred", "kv_readbacks_forced",
            "kv_blocks_evicted", "kv_blocks_offloaded",
            "kv_offload_backpressure_waits", "kv_offload_us",
            "keys_cached", "keys_attended", "keys_read",
            "blocks_behind_wave", "blocks_plain", "loop_passes",
            "loop_host_us", "loop_stalls", "loop_stall_us"), 0)
        # The pass of the loop that is under way: what each of its
        # tiles has taken so far, by the span's name, and what it spent
        # waiting for a result or a request (`_end_pass`).
        self._pass: Dict[str, float] = {}
        self._pass_waited = 0.0
        # What the model counts while it runs (nothing, for most) joins
        # the totals under the model's own names, which one abstract
        # evaluation of a decode step gives. The same evaluation says
        # in which dtype the model hands over its logits (the sample
        # program is compiled for it).
        step = jnp.zeros((self.n_slots, self._step_len), jnp.int32)
        logits, _, counts = jax.eval_shape(
            lambda p, c: self._served.forward(p, step, cfg, c, step[:, 0],
                                              0),
            params, self.cache)
        self._logits_dtype = logits.dtype
        self._count_names = tuple(sorted(counts))
        self._totals.update(dict.fromkeys(self._count_names, 0))

        # Compiled programs. Prefill is per-slot (batch 1, bucketed T);
        # decode covers all slots at T=1. Params are explicit arguments —
        # closing over them would bake the full weight set into every
        # compiled program as constants (one 2.5GB copy per prefill
        # bucket), exploding compile time and HBM.
        # Pin the small-argument shardings at the jit boundary: the
        # serving loop alternates host-built arrays (admission refreshes
        # temps/last) with device carries (pipelined decode outputs),
        # whose differing shardings otherwise key DISTINCT compiled
        # variants of what should be one program per bucket.
        s1 = jax.sharding.SingleDeviceSharding(jax.devices()[0])
        # Canonicalize params too: weights initialized onto a training
        # mesh carry a NamedSharding whose axes leak into every jit
        # OUTPUT's aval type; warmup (plain inputs) and the serving loop
        # (mesh-typed carries) then trace as DIFFERENT signatures and
        # each program compiles twice. One engine = one device = one
        # sharding vocabulary. (No-op copy when already single-device.)
        self.params = jax.device_put(self.params, s1)
        self.cache = jax.device_put(self.cache, s1)
        self._rng = jax.device_put(self._rng, s1)
        self._dev_last = jax.device_put(
            jnp.zeros(self.n_slots, jnp.int32), s1)
        self._dev_lengths = jax.device_put(
            jnp.zeros(self.n_slots, jnp.int32), s1)
        self._prefill = jax.jit(
            self._prefill_impl, donate_argnums=(1,),
            static_argnums=(6,),  # t — positional: pjit rejects kwargs
            in_shardings=(None, s1, s1, s1, s1, s1),  # with in_shardings
            out_shardings=(s1, s1))
        self._make_step_programs(s1)
        # AOT-compiled executables, filled by warmup(): the bucket
        # ladder compiles CONCURRENTLY (XLA releases the GIL; compiles
        # parallelize across cores) and the serving path then calls the
        # compiled objects directly — no jit-cache recompile behind the
        # first request. A bucket warmup did not cover compiles lazily
        # through the jit function.
        self._prefill_exec: Dict[int, Any] = {}
        self._decode_exec = None
        self._sample_exec = None
        self._s1 = s1

        # Prefix/KV cache: the PrefixCache decision core decides which
        # blocks exist / are pinned / get evicted; _kv_store holds the
        # actual host-side KV payloads keyed by block generation id
        # (evicted payloads fall to the shm-plane warm tier).
        self.block_tokens = max(1, int(ray_config.llm_kv_block_tokens))
        self.prefix_cache: Optional[PrefixCache] = None
        if ray_config.llm_prefix_cache and not any(self._is_state) \
                and self.block_tokens < self.max_seq:
            self.prefix_cache = PrefixCache(
                ray_config.llm_prefix_cache_bytes, self.block_tokens)
        self._kv_store: Dict[int, tuple] = {}
        per_token = sum(
            x.size * x.dtype.itemsize // (x.shape[1] * x.shape[2])
            for x in self._leaves)
        self._block_nbytes = per_token * self.block_tokens
        self._chain_seed = self._seed_for(model)
        self._c_shm_offloads = perf_stats.counter("llm_kv_shm_offloads")
        self._c_shm_restores = perf_stats.counter("llm_kv_shm_restores")
        # Read-backs on their way to the host, oldest first, and which
        # of them holds a block's payload. Their device arrays may hold
        # `_readback_cap` bytes together, an eighth of the slot cache
        # and never less than one slot's rows, the largest one request
        # can need (268 MB at 32 slots x 1024 of Mistral-7B's 64 KB a
        # token: eight median prompts at bucket 512); at the cap the
        # oldest is waited for. The lock is for `stop()` and
        # `swap_params`, which force them from another thread.
        self._readbacks: "collections.deque[_Readback]" = \
            collections.deque()
        self._readback_of: Dict[int, _Readback] = {}
        self._readback_bytes = 0
        self._readback_cap = per_token * self.max_seq * max(
            1, self.n_slots // 8)
        self._readback_lock = threading.Lock()
        # KV copy programs: read-back takes `rows` (static: one program
        # a prefill bucket, compiled by warmup) of a slot's region from
        # a traced offset; copy-in writes one block, [layers_i, B, ...]
        # a leaf.
        self._read_rows_j = jax.jit(
            self._read_rows_impl, static_argnums=(3,),
            in_shardings=(s1, s1, s1), out_shardings=s1)
        self._read_rows_exec: Dict[int, Any] = {}
        self._write_block_j = jax.jit(
            self._write_block_impl, donate_argnums=(0,),
            in_shardings=(s1, s1, s1, s1), out_shardings=s1)

    def _make_step_programs(self, s1):
        self._decode = jax.jit(
            self._decode_impl, donate_argnums=(1,),
            in_shardings=(None, s1, s1, s1, s1, s1, s1),
            out_shardings=(s1, s1, s1, s1, s1, s1))
        # First-token sampling for an admission wave — FIXED shape
        # [n_slots, vocab] (padded) so it is ONE program compiled at
        # warmup, not a variant per distinct admitted-count. It also
        # writes the admitted slots into the decode carries.
        self._sample_admitted = jax.jit(
            self._sample_admitted_impl,
            in_shardings=(s1,) * 7, out_shardings=(s1,) * 4)

    def _seed_for(self, model: str) -> str:
        """Chain-key seed: model identity + the KV-shape fingerprint.
        Two chains share keys only when the cached bytes are
        interchangeable — same model, same layout — which is what makes
        the shm tier safe to share across replicas."""
        c = self.cfg
        layout = ",".join(
            "x".join(map(str, (x.shape[0],) + x.shape[3:])) + f":{x.dtype}"
            for x in self._leaves)
        return (f"{model}|{c.n_layers}x{c.dim}x{c.max_seq_len}|{layout}|"
                f"{self.block_tokens}")

    def warmup(self, max_prompt_len: Optional[int] = None) -> float:
        """Compile every program the serving path needs BEFORE the first
        request (deploy-time AOT): prefill at each bucket (`prefill_bucket`)
        up to ``max_prompt_len`` (default max_seq) plus the decode body and
        the admission sampler. Must run before :meth:`start`.

        The bucket ladder compiles CONCURRENTLY: each program is
        lowered and compiled on a thread pool (XLA compilation drops the
        GIL and parallelizes across host cores), so a first-ever deploy
        pays roughly the LONGEST compile, not the sum of the ladder.
        The compiled executables then serve traffic directly (and each
        runs once here to validate + touch device memory). Returns the
        wall seconds spent — with the persistent compilation cache this
        is seconds on the first deploy of a config and near-zero
        afterwards. A program that does not compile raises here, at
        deploy time."""
        assert self._thread is None or not self._thread.is_alive(), \
            "warmup() must run before the engine loop starts"
        t0 = time.perf_counter()
        limit = min(max_prompt_len or self.max_seq, self.max_seq)
        buckets = [b for b in bucket_ladder(limit, self.max_seq)
                   if b % self._step_len == 0]
        self._compile_ladder_concurrent(buckets)
        last = None
        for bucket in buckets:
            self.cache, last = self._run_prefill(
                np.zeros((1, bucket), np.int32), 0, 1, 0, bucket)
        if self.prefix_cache is not None:
            # Touch the KV copy programs so the first cache hit or
            # read-back doesn't pay a mid-serving compile.
            for rows in sorted(self._read_rows_exec):
                self._run_read_rows(0, 0, rows)
            block = tuple(
                jnp.zeros((x.shape[0], self.block_tokens) + x.shape[3:],
                          x.dtype) for x in self._leaves)
            self.cache = self._write_block_j(
                self.cache, block, np.int32(0), np.int32(0))
        np.asarray(self._warm_step_programs(last))  # wait for the device
        # Warmup wrote garbage KV into slot 0; lengths stay 0 so every
        # slot still reads as empty when serving starts.
        return time.perf_counter() - t0

    def _warm_step_programs(self, last):
        """Run once the two programs of a step, on padding, and return
        an array of the last: the wave's program (every row is padding,
        so the carries would come back as they went) and the decode
        block."""
        _firsts, self._rng, _last, _lens = self._run_sample(
            (last,) * self.n_slots, np.zeros(self.n_slots, np.float32),
            np.full(self.n_slots, self.n_slots, np.int32),
            np.zeros(self.n_slots, np.int32))
        (self.cache, toks, _last, _lens, self._rng,
         _counts) = self._run_decode(
            jnp.zeros(self.n_slots, jnp.int32),
            jnp.zeros(self.n_slots, jnp.int32),
            jnp.zeros(self.n_slots, jnp.float32),
            jnp.zeros(self.n_slots, jnp.int32))
        return toks

    def _compile_ladder_concurrent(self, buckets) -> None:
        """AOT-compile every serving program on a thread pool."""
        import os
        from concurrent.futures import ThreadPoolExecutor

        import jax.numpy as _jnp

        def aval(shape, dtype=_jnp.int32):
            return jax.ShapeDtypeStruct(shape, dtype)

        params_avals = jax.tree_util.tree_map(
            lambda x: aval(x.shape, x.dtype), self.params)
        cache_avals = jax.tree_util.tree_map(
            lambda x: aval(x.shape, x.dtype), self.cache)
        rng_aval = aval(self._rng.shape, self._rng.dtype)

        def compile_prefill(bucket):
            lowered = self._prefill.lower(
                params_avals, cache_avals, aval((1, bucket)),
                aval(()), aval(()), aval(()), bucket)
            return bucket, lowered.compile()

        def compile_decode():
            # What the step's layers read of their parameters, and how:
            # where they lie in their stacks (`ops.stacked_product`) or
            # as the layer scan's slices, as the trace counted them.
            with critical_path.span("setup.compile_decode") as sp, \
                    stacked_product.weights_read() as read:
                lowered = self._decode.lower(
                    params_avals, cache_avals, *self._decode_avals(aval),
                    rng_aval)
                sp.set(weights_in_place_bytes=read["in_place"],
                       weights_sliced_bytes=read["sliced"])
                return "decode", lowered.compile()

        def compile_sample():
            lowered = self._sample_admitted.lower(
                *self._sample_avals(aval, rng_aval))
            return "sample", lowered.compile()

        def compile_read_rows(rows):
            lowered = self._read_rows_j.lower(
                cache_avals, aval(()), aval(()), rows)
            return ("read_rows", rows), lowered.compile()

        jobs = [lambda b=b: compile_prefill(b) for b in buckets]
        jobs += [compile_decode, compile_sample]
        if self.prefix_cache is not None:
            # A read-back takes the bucket that holds its blocks' rows.
            jobs += [lambda b=b: compile_read_rows(b) for b in buckets
                     if b >= self.block_tokens]
        workers = min(len(jobs), max(2, os.cpu_count() or 4))
        with ThreadPoolExecutor(max_workers=workers,
                                thread_name_prefix="aot-compile") as pool:
            for key, compiled in pool.map(lambda fn: fn(), jobs):
                if key == "decode":
                    self._decode_exec = compiled
                elif key == "sample":
                    self._sample_exec = compiled
                elif isinstance(key, tuple):
                    self._read_rows_exec[key[1]] = compiled
                else:
                    self._prefill_exec[key] = compiled

    def _decode_avals(self, aval):
        """The decode program's arguments between the cache and the
        rng, as shapes."""
        n = self.n_slots
        return aval((n,)), aval((n,)), aval((n,), jnp.float32), aval((n,))

    def _sample_avals(self, aval, rng_aval):
        # Prefill hands over its last-position logits as the model
        # gives them.
        n = self.n_slots
        return ((aval((self.cfg.vocab_size,), self._logits_dtype),) * n,
                aval((n,), jnp.float32), rng_aval, aval((n,)), aval((n,)),
                aval((n,)), aval((n,)))

    # -- compiled-or-jit call shims --------------------------------------
    #
    # An AOT executable is called as compiled: if its arguments drifted
    # from what warmup lowered (aval or sharding), that is a bug and it
    # raises — it is never turned into a recompile in the serving window.

    def _run_prefill(self, tokens, slot, length, start, bucket):
        # Host values ride the call, as `_run_read_rows`' do.
        slot, length, start = np.int32(slot), np.int32(length), \
            np.int32(start)
        compiled = self._prefill_exec.get(bucket)
        if compiled is not None:
            return compiled(self.params, self.cache, tokens, slot, length,
                            start)
        return self._prefill(self.params, self.cache, tokens, slot,
                             length, start, bucket)

    def _run_decode(self, last, lengths, temps, topks):
        fn = self._decode_exec or self._decode
        return fn(self.params, self.cache, last, lengths, temps, topks,
                  self._rng)

    def _run_sample(self, rows, temps, slots, lengths):
        fn = self._sample_exec or self._sample_admitted
        return fn(rows, temps, self._rng, self._dev_last,
                  self._dev_lengths, slots, lengths)

    def _run_read_rows(self, slot, start, rows):
        # Host scalars ride the call: a third off the dispatch against
        # two device scalars made first (0.79 against 1.16 ms, v5e).
        slot, start = np.int32(slot), np.int32(start)
        compiled = self._read_rows_exec.get(rows)
        if compiled is not None:
            return compiled(self.cache, slot, start)
        return self._read_rows_j(self.cache, slot, start, rows)

    # -- compiled bodies -------------------------------------------------

    def _sample_admitted_impl(self, rows, temps, rng, last, lengths,
                              slots, prompt_lengths):
        """A wave's rows, one an admitted request: `rows`, n_slots
        prefill outputs of [vocab] logits (stacked here: an eager
        `jnp.stack` of arrays still being computed waits for them),
        and temps → first token per row (greedy at temp 0), also
        written with the row's prompt length into the decode carries
        `last` and `lengths` at the row's slot, so that the next decode
        block is fed from the device. Rows beyond the admitted count
        are padding: their slot is `n_slots`, which the carries' update
        drops, and the host ignores their token."""
        logits = jnp.stack(rows)  # [n_slots, vocab]
        rng, sub = jax.random.split(rng)
        sampled = jax.random.categorical(
            sub, logits / jnp.maximum(temps, 1e-6)[:, None])
        firsts = jnp.where(temps > 0, sampled,
                           logits.argmax(-1)).astype(jnp.int32)
        last = last.at[slots].set(firsts, mode="drop")
        lengths = lengths.at[slots].set(prompt_lengths, mode="drop")
        return firsts, rng, last, lengths

    def _prefill_impl(self, params, cache, tokens, slot, length, start, t):
        """tokens: [1, t] padded prompt tail; writes KV for one slot
        beginning at absolute position `start` (0 for a full prefill;
        the matched-prefix length when cached KV blocks were copied in
        ahead of this call), returns logits at the last real position
        [vocab]."""
        slot_cache = jax.tree.map(
            lambda x: jax.lax.dynamic_slice_in_dim(x, slot, 1, axis=1),
            cache)
        last, new_slot_cache, _ = self._served.forward(
            params, tokens, self.cfg, slot_cache,
            jnp.full((1,), start, jnp.int32), length - 1)
        cache = jax.tree.map(
            lambda x, new: jax.lax.dynamic_update_slice_in_dim(
                x, new, slot, axis=1), cache, new_slot_cache)
        return cache, last[0]

    @staticmethod
    def _layer_parts(leaf, rows) -> int:
        """In how many equal parts a read-back hands over a leaf's
        layers: as few as keep an array of `rows` rows within
        `_D2H_ARRAY_BYTES`."""
        layers = leaf.shape[0]
        nbytes = rows * leaf.dtype.itemsize * int(np.prod(leaf.shape[3:]))
        return next((p for p in range(1, layers) if layers % p == 0
                     and nbytes * layers // p <= _D2H_ARRAY_BYTES), layers)

    def _read_rows_impl(self, cache, slot, start, rows):
        """Read `rows` tokens' KV out of a slot's region from token
        offset `start` → row leaf after row leaf of the cache its
        parts, each [layers_i / p, rows, ...] (`_layer_parts`)."""
        out = []
        for x, state in zip(jax.tree.leaves(cache), self._is_state):
            if state:
                continue
            # [layers_i, slots, S, ...]
            tail = (0,) * (x.ndim - 3)
            blk = jax.lax.dynamic_slice(
                x, (0, slot, start) + tail,
                (x.shape[0], 1, rows) + x.shape[3:])
            out += jnp.split(blk[:, 0], self._layer_parts(x, rows))
        return tuple(out)

    def _write_block_impl(self, cache, block, slot, start):
        """Write one KV block (`block`: [layers_i, B, ...] a row leaf
        of the cache, in the leaves' order) into a slot's region at
        token offset `start`."""
        leaves, tree = jax.tree.flatten(cache)
        block = iter(block)
        return tree.unflatten([
            x if state else jax.lax.dynamic_update_slice(
                x, next(block)[:, None],
                (0, slot, start) + (0,) * (x.ndim - 3))
            for x, state in zip(leaves, self._is_state)])

    def _decode_impl(self, params, cache, last_tokens, lengths, temps,
                     topks, rng):
        """`decode_steps` tokens for every slot per dispatch, via an
        in-program `lax.scan` (vLLM-style multi-step decoding): one
        device execution amortizes the per-dispatch overhead over K
        tokens. Returns tokens [slots, K] and, last, what the model
        counted over the K steps: int32 [names], names sorted, or () of
        a model that counts nothing."""
        def step(carry, _):
            cache, tokens, lengths, rng = carry
            # Clamp for retired slots that keep computing until their
            # slot is re-admitted (pipelined decode fetches lag a block):
            # their writes wrap at the last position instead of OOB.
            lengths = jnp.minimum(lengths, self.max_seq - 2)
            logits, cache, counts = self._served.forward(
                params, tokens[:, None], self.cfg, cache, lengths, 0)
            counts = tuple(counts[name] for name in self._count_names)
            logits = logits.astype(jnp.float32)  # [slots, vocab]
            greedy = logits.argmax(-1)
            # Per-slot top-k truncation: threshold at each slot's k-th
            # largest logit (k clamped to _TOP_K_MAX — lax.top_k needs a
            # static k, so one sorted prefix serves every slot).
            kth_vals = jax.lax.top_k(logits, _TOP_K_MAX)[0]
            idx = jnp.clip(topks - 1, 0, _TOP_K_MAX - 1)
            thresh = jnp.take_along_axis(kth_vals, idx[:, None], axis=1)
            truncated = jnp.where(logits < thresh, -jnp.inf, logits)
            sample_logits = jnp.where((topks > 0)[:, None], truncated,
                                      logits)
            rng, sub = jax.random.split(rng)
            sampled = jax.random.categorical(
                sub, sample_logits / jnp.maximum(temps, 1e-6)[:, None])
            next_tokens = jnp.where(temps > 0, sampled,
                                    greedy).astype(jnp.int32)
            return (cache, next_tokens, lengths + 1, rng), (next_tokens,
                                                            counts)

        (cache, last, lengths, rng), (toks, counts) = jax.lax.scan(
            step, (cache, last_tokens, lengths, rng), None,
            length=self.decode_steps)
        if counts:
            counts = jnp.stack(counts, -1).sum(0, dtype=jnp.int32)
        # Device-side carries (last/lengths) let the NEXT decode dispatch
        # before this block's tokens reach the host (pipelined decode).
        return cache, toks.T, last, lengths, rng, counts  # toks [slots, K]

    # -- public API ------------------------------------------------------

    def start(self):
        # Under the lock: concurrent generate() callers must never spawn
        # two engine loops — dueling loops double-assign slots and feed
        # the donated cache twice, silently losing requests.
        with self._lock:
            if self._thread is None or not self._thread.is_alive():
                self._running.set()
                self._thread = threading.Thread(
                    target=self._loop, daemon=True, name="llm-engine")
                self._thread.start()

    def stop(self):
        self._running.clear()
        # Let the loop leave its current device fetch before interpreter
        # teardown (a daemon thread cancelled mid-fetch can abort the
        # process with pthread noise).
        t = self._thread
        if t is not None and t.is_alive() \
                and t is not threading.current_thread():
            t.join(timeout=10)
        # What the loop had not finished goes to the host store now.
        self._force_readbacks()

    def generate(self, prompt_ids: List[int],
                 params: Optional[SamplingParams] = None,
                 stream: bool = False, *,
                 model: Optional[str] = None,
                 priority: int = 1,
                 job: str = "default",
                 beat_s: Optional[float] = None,
                 with_steps: bool = False):
        """Blocking generate (or an iterator of tokens with stream=True).

        With `with_steps` an item is (token, step): of a model that
        generates by blocks, the denoising step of its block at which
        the token was fixed; None of a model that yields a token a
        step. A block's tokens come together.

        With `beat_s` the iterator yields None where the request has
        waited that long for its first token and the loop has run
        decode steps meanwhile: it is queued behind full slots, not
        hung. A loop that steps for nobody gives no such sign."""
        prompt = list(prompt_ids)
        cap = self.max_seq - 1
        if len(prompt) > cap:
            raise PromptTooLongError(len(prompt), cap)
        params = params or SamplingParams()
        req = _Request(
            request_id=next(self._req_counter), prompt=prompt,
            params=params, out_queue=queue.Queue(),
            known=self._known_tokens(prompt, params),
            t_arrival=critical_path.clock(),
            model=model, priority=max(0, min(2, int(priority))), job=job,
            # Stamped on the CALLING thread (the replica's task context
            # is thread-local; the engine loop below has none).
            trace_id=(critical_path.ambient_trace_id() or "")
            if critical_path.enabled() else "")
        self._queue.put(req)
        self.start()

        def token_iter():
            wait, steps = beat_s, self._totals["decode_steps"]
            n = 0
            while True:
                try:
                    item = req.out_queue.get(timeout=wait)
                except queue.Empty:
                    steps, before = self._totals["decode_steps"], steps
                    if steps != before:
                        yield None
                    continue
                if item is None:
                    return
                n += 1
                if not n % LAG_SAMPLE_EVERY:
                    # The loop's put to this thread's wake-up.
                    critical_path.record_stage(
                        None, "stream.wake",
                        critical_path.clock() - req.t_put)
                wait = None  # admitted: every step brings a token
                # (A model that generates by blocks queues pairs.)
                token, step = item if isinstance(item, tuple) \
                    else (item, None)
                yield (token, step) if with_steps else token

        if stream:
            return token_iter()
        return list(token_iter())

    def _known_tokens(self, prompt, params) -> int:
        """How many of the prompt's last tokens are not prefilled but
        open the request's first block: none, of a model that yields a
        token a step. Where the request's parameters are judged."""
        return 0

    def metrics(self) -> Dict[str, Any]:
        with self._lock:
            out = {
                "active_slots": int(self._active.sum()),
                "free_slots": len(self._free_slots),
                "queued": self._queue.qsize(),
                "model": self.model,
                # AOT executables warmup left: prefill ladder + decode
                # + admission sampler + the read-back's gathers.
                "compiled_programs": len(self._prefill_exec)
                + (self._decode_exec is not None)
                + (self._sample_exec is not None)
                + len(self._read_rows_exec),
                # Since the engine started: decode steps run and the
                # slot-steps of them that held a request; tokens handed
                # to clients (first tokens and kept decode tokens) and
                # decode tokens computed for nobody; prompt tokens
                # prefilled and the bucket sizes paid for them; waves
                # of admission, those of them that found a decode block
                # in flight and left it there, the slot-steps of blocks
                # dropped because the block was older than the slot's
                # request (part of the discarded), and requests
                # admitted; KV blocks and
                # bytes read back for the prefix cache (counted when
                # the block is created), the requests whose read-back
                # left the wave, and those of them that were waited
                # for (a hit or an eviction of a block still on its
                # way, the cap on pending bytes, stop, a model swap);
                # over the active slots of every decode dispatch the
                # keys their caches held and those of them the step
                # attended (fewer where the model selects keys:
                # `ServedModel.keys_attended`) and the rows a layer's
                # attention fetched for them (`ServedModel.keys_read`;
                # 0 for a model that names none); the blocks handed over
                # behind another, those with a wave's prefills in front
                # of them and those without (one `engine.block_gap.*`
                # record each); what the prefix cache's admissions
                # evicted, what of it the warm tier took, the waits for
                # room there and the microseconds in its `maybe_put`;
                # the loop's passes that did work and their host time
                # (one `engine.loop_host` record each), and those of
                # them that reached `_STALL_S` (one
                # `engine.loop_stall.*` each); and what the model
                # itself counted in its decode blocks, under its names
                # (an expert layer's `pairs_held`, `pairs_routed`,
                # `pair_overflows`, `experts_touched`,
                # `experts_held_steps`).
                "totals": dict(self._totals),
            }
        if self.prefix_cache is not None:
            out["kv_cache"] = self.prefix_cache.stats()
        return out

    # -- engine loop -----------------------------------------------------

    def _loop(self):
        """The loop is tiled by `critical_path` spans, so that a
        profiler's trace shows under each of the device's idle gaps
        what the loop was doing: `engine.admit_wave` (with its parts as
        children), `engine.decode_dispatch`, `engine.token_fetch` (the
        wait for a decode block, and for a wave's first tokens with
        their delivery as its child `engine.first_tokens`),
        `engine.consume_block`, `engine.idle_wait`. Between two blocks
        handed over one behind the other lies one thin record,
        `engine.block_gap.wave` or `engine.block_gap.plain`
        (`_consume_block`), and at the end of every pass that did work
        one more, `engine.loop_host`, what the pass cost the host
        (`_end_pass`).

        A wave only dispatches, so the decode pipeline runs through it:
        wave (prefills and the sample program, queued behind block N in
        flight), dispatch of block N+1, fetch and delivery of block N,
        fetch and delivery of the wave's first tokens."""
        self._temps_arr = np.zeros(self.n_slots, np.float32)
        self._topks_arr = np.zeros(self.n_slots, np.int32)
        while self._running.is_set():
            t_pass = critical_path.clock()
            admitted = self._admit()
            if self._active.any():
                self._decode_once()
            else:
                # Drop any in-flight block for fully-retired slots.
                self._flush_pending()
                if not admitted:
                    with critical_path.span("engine.idle_wait") as idle:
                        self._finish_readbacks()
                        try:
                            req = self._queue.get(timeout=0.05)
                            self._queue.put(req)
                        except queue.Empty:
                            pass
                    self._pass_waited += idle.dur_s
            self._end_pass(t_pass)

    def _tile(self, sp) -> None:
        """`sp`, closed, was a tile of the pass under way (nothing,
        with the recorder off: its spans take no time)."""
        if sp.dur_s:
            self._pass[sp.name] = self._pass.get(sp.name, 0.0) + sp.dur_s

    def _end_pass(self, t_pass: float) -> None:
        """The end of a pass of `_loop` that began at `t_pass`. A pass
        that did work (a wave, a decode block) leaves one thin record,
        `engine.loop_host`: its wall time less what it spent waiting
        for a result or a request, which is the two fetches' wait for
        the device and `engine.idle_wait`. Everything else is the
        host's, on purpose: dispatches, hand-overs, a forced
        read-back's copy, `maybe_put`'s sleeps, a wait for a lock. With
        the profiler off it is the reading of what the loop costs.

        A pass whose host time reaches `_STALL_S` leaves a second
        record of that duration, named by the tile that held most of
        it: `engine.loop_stall.prefix_admit` where that child held most
        of the wave, `.admit_wave`, `.decode_dispatch`,
        `.consume_block`, `.first_tokens`, or `.other` for what lies
        between the tiles. Beside it in the ring lie the tiles' own
        spans and the beats of `process.wake_late`, which say whether
        the loop blocked or the process stood still."""
        tiles, waited = self._pass, self._pass_waited
        self._pass_waited = 0.0
        if not tiles:
            return  # it only idled
        host_s = max(0.0, critical_path.clock() - t_pass - waited)
        critical_path.record_stage(None, "engine.loop_host", host_s)
        totals, host_us = self._totals, round(host_s * 1e6)
        totals["loop_passes"] += 1
        totals["loop_host_us"] += host_us
        if host_s >= _STALL_S:
            inner = tiles.pop("engine.prefix_admit", 0.0)
            tiles["other"] = host_s - sum(tiles.values())
            top = max(tiles, key=tiles.get)
            if top == "engine.admit_wave" and 2 * inner > tiles[top]:
                top = "engine.prefix_admit"
            critical_path.record_stage(None, _STALL_NAMES[top], host_s)
            totals["loop_stalls"] += 1
            totals["loop_stall_us"] += host_us
        tiles.clear()

    def _serve_bucket(self, t_real: int) -> int:
        """Smallest compiled bucket that fits `t_real` tokens. The old
        code keyed `_run_prefill` on the exact bucket, so a
        request just over `warmup_max_prompt_len` missed the AOT ladder
        and paid a mid-serving compile even though a LARGER compiled
        bucket could serve it; now any bucket ≤ the compiled max
        serves from the ladder."""
        b = min(prefill_bucket(t_real), self.max_seq)
        if b in self._prefill_exec or not self._prefill_exec:
            return b
        bigger = [x for x in self._prefill_exec if x >= b]
        return min(bigger) if bigger else b

    def _admit(self) -> bool:
        if self._queue.empty() or not self._free_slots:
            return False
        with critical_path.span("engine.admit_wave") as wave:
            admitted = self._admit_wave(wave)
        self._tile(wave)
        return admitted

    def _admit_wave(self, wave) -> bool:
        """One wave of admission. It dispatches and returns: the
        prefills, the first tokens' sampling and the decode carries'
        update are programs queued on the device behind the decode
        block in flight, and nothing here waits for a result. The first
        tokens reach the host in `_deliver_first_tokens`."""
        with critical_path.span("engine.flush_pending"):
            # The block in flight stays in flight. Its tokens for a
            # slot admitted here belong to the request before, and
            # `_consume_block` drops them by the block's owners.
            behind = self._pending_block is not None
        totals = self._totals
        drained: List[_Request] = []
        while True:
            try:
                drained.append(self._queue.get_nowait())
            except queue.Empty:
                break
        # Priority classes decide who gets the scarce slots at the shed
        # point: interactive (0) outranks normal (1) outranks batch (2);
        # FIFO within a class via the monotonic request id.
        drained.sort(key=lambda r: (r.priority, r.request_id))
        staged = []  # (req, slot, last_logits_ref, chain)
        leftover: List[_Request] = []
        for req in drained:
            if not self._free_slots:
                leftover.append(req)
                continue
            # What is prefilled: the prompt; its whole blocks, of a
            # model that generates by blocks.
            prompt = req.prompt[:len(req.prompt) - req.known]
            slot = self._free_slots.pop()
            # Stage: admit = time spent queued for a slot.
            t_admit = critical_path.clock()
            critical_path.record_stage(req.trace_id, "llm.admit",
                                       t_admit - req.t_arrival)
            # Prefix-cache fast path: copy matched KV blocks straight
            # into the slot, then prefill ONLY the tail at the tail's
            # bucket, starting at the matched offset.
            with critical_path.span("engine.prefix_copy_in") as sp:
                m_tok, chain = self._prefix_copy_in(req, slot, prompt)
                sp.set(matched_tokens=m_tok)
            req.t_kv_done = critical_path.clock()
            critical_path.record_stage(req.trace_id, "llm.kv_lookup",
                                       req.t_kv_done - t_admit)
            tail = prompt[m_tok:]
            t_tail = len(tail)
            bucket = self._serve_bucket(t_tail)
            last_logits = None
            # (A prompt shorter than a block has nothing to prefill.)
            if t_tail or self._step_len == 1:
                with critical_path.span("engine.prefill_dispatch",
                                        real=t_tail, bucket=bucket):
                    tokens = np.zeros((1, bucket), np.int32)
                    tokens[0, :t_tail] = tail
                    self.cache, last_logits = self._run_prefill(
                        tokens, slot, t_tail, m_tok, bucket)
                totals["prefill_tokens_real"] += t_tail
                totals["prefill_tokens_bucketed"] += bucket
            with self._lock:
                req.slot = slot
                self._slot_req[slot] = req
                self._lengths[slot] = len(prompt)
                self._active[slot] = True
                self._temps_arr[slot] = req.params.temperature
                self._topks_arr[slot] = max(0, min(req.params.top_k,
                                                   _TOP_K_MAX))
            staged.append((req, slot, last_logits, chain))
        for req in leftover:
            self._queue.put(req)
        wave.set(admitted=len(staged), left_over=len(leftover),
                 behind_block=int(behind))
        if not staged:
            return False
        totals["admit_waves"] += 1
        totals["admit_waves_behind_block"] += behind
        self._wave_dispatched = True  # the next block runs behind them
        totals["admissions"] += len(staged)
        self._dispatch_first_tokens(staged)
        # The prefix cache admits the prompts' blocks after the prefill
        # is dispatched, and here only starts their read-back: one
        # gather and an asynchronous copy a request, queued behind that
        # prefill; `_decode_once` finishes it on the host while a decode
        # block runs. Safe ordering: the slot cannot be admitted again
        # before a LATER wave, so the KV bytes being gathered are this
        # request's prefill output. The one stretch of a wave that may
        # wait (a forced read-back, the warm tier's back-pressure) has
        # a span of its own, with what it did as the rise of the totals
        # across it.
        if self.prefix_cache is not None:
            with critical_path.span("engine.prefix_admit") as sp:
                before = [totals[name] for name in _PREFIX_ADMIT.values()]
                for req, slot, _logits, chain in staged:
                    self._prefix_admit(req, slot, chain)
                sp.set(**{attr: totals[name] - was for (attr, name), was
                          in zip(_PREFIX_ADMIT.items(), before)})
            self._tile(sp)
        return True

    def _dispatch_first_tokens(self, staged):
        """ONE device-side sampling for the whole wave, padded to
        n_slots rows so the program has one fixed shape, compiled
        once at warmup. The same program puts each first token and
        prompt length into the decode carries, and the tokens' copy
        to the host is started here and waited for behind the next
        decode dispatch."""
        with critical_path.span("engine.sample_dispatch"):
            pad = self.n_slots - len(staged)
            rows = tuple(s[2] for s in staged) + (staged[0][2],) * pad
            temps = np.zeros(self.n_slots, np.float32)
            slots = np.full(self.n_slots, self.n_slots, np.int32)
            lengths = np.zeros(self.n_slots, np.int32)
            for i, (req, slot, _logits, _chain) in enumerate(staged):
                temps[i] = req.params.temperature
                slots[i] = slot
                lengths[i] = len(req.prompt)
            (firsts, self._rng, self._dev_last,
             self._dev_lengths) = self._run_sample(
                rows, temps, slots, lengths)
            firsts.copy_to_host_async()
            self._first_tokens = (
                firsts, [(req, slot) for req, slot, _, _ in staged])

    def _decode_once(self):
        # The fed token occupies absolute position `lengths` (prompt is
        # 0..len-1, first generated token sits at len, etc.). Dispatch
        # block N+1 from the device-side carries, THEN fetch block N —
        # the host round-trip overlaps the next block's compute.
        active = int(self._active.sum())
        attrs = {}
        if critical_path.enabled():
            lengths = self._lengths[self._active]
            attrs = {"keys_cached": int(lengths.sum()),
                     "keys_attended": int(self._served.keys_attended(
                         self.cfg, lengths).sum())}
            if self._served.keys_read is not None:
                # The fed token's row (a block's rows, read once for
                # all of them) is written before the step reads.
                attrs["keys_read"] = int(np.minimum(
                    self._served.keys_read(self.cfg,
                                           lengths + self._step_len),
                    self.max_seq).sum())
            for name, n in attrs.items():
                self._totals[name] += n
        with critical_path.span("engine.decode_dispatch", active=active,
                                n_slots=self.n_slots,
                                keys_reserved=self.n_slots * self.max_seq,
                                **attrs) as dispatch:
            prev = self._pending_block
            next_tokens, counts = self._dispatch_decode()
            self._pending_block = (next_tokens, [
                self._slot_req.get(slot) for slot in range(self.n_slots)],
                counts, self._wave_dispatched)
            self._wave_dispatched = False
            # The device has a block to run and the host nothing to do
            # but wait for the one before it: the read-backs' host half.
            self._finish_readbacks(
                self.max_seq // self.block_tokens,
                prev[0] if prev is not None else None)
        self._tile(dispatch)
        self._totals["decode_steps"] += self.decode_steps
        self._totals["active_slot_steps"] += active * self.decode_steps
        if prev is None:
            self._handed_over = None  # a gap lies between two blocks
        else:
            self._consume_block(self._fetch_tokens(prev[0]), *prev[1:])
        # Dispatched before the block just dispatched, so they reach
        # their clients before that block is waited for.
        self._deliver_first_tokens()

    def _dispatch_decode(self):
        """Dispatch a decode block from the device's carries: (what the
        host will fetch of it, what the model counted)."""
        (self.cache, next_tokens, self._dev_last, self._dev_lengths,
         self._rng, counts) = self._run_decode(
            self._dev_last, self._dev_lengths,
            jnp.asarray(self._temps_arr), jnp.asarray(self._topks_arr))
        return next_tokens, counts

    def _flush_pending(self):
        prev, self._pending_block = self._pending_block, None
        if prev is not None:
            self._consume_block(self._fetch_tokens(prev[0]), *prev[1:])

    def _fetch_tokens(self, block):
        """A decode block's sampled tokens to the host: the wait for
        the block to finish on the device."""
        with critical_path.span("engine.token_fetch") as fetch:
            tokens = np.asarray(block)
        self._pass_waited += fetch.dur_s
        return tokens

    def _consume_block(self, next_host, owners, counts=(),
                       behind_wave=False):
        """Hand a block's tokens to the requests it was dispatched for.
        `owners` are the slots' requests at its dispatch: a slot that
        holds another request by now (admitted while the block was in
        flight, into a slot retired before) gets none of them.
        `counts` is what the model counted in the block, ready with its
        tokens: onto the span and into the totals.

        Where the block before this one was in flight at its dispatch,
        the time from that block's hand-over to the end of this one is
        recorded, the gap every active slot's client is dealt: as
        `engine.block_gap.wave` where a wave's prefills were dispatched
        in front of this block (`behind_wave`), as
        `engine.block_gap.plain` where none were."""
        kept = stale = 0
        counted = dict(zip(self._count_names, map(int, np.asarray(counts))))
        with critical_path.span("engine.consume_block") as sp, self._lock:
            for slot in np.nonzero(self._active)[0]:
                req = self._slot_req[slot]
                if owners[slot] is not req:
                    stale += next_host.shape[1]
                    continue
                # Walk this slot's K-token block; once the request
                # finishes mid-block the remaining tokens are padding
                # compute and are discarded.
                for k in range(next_host.shape[1]):
                    tok = int(next_host[slot, k])
                    req.tokens.append(tok)
                    if not len(req.tokens) % LAG_SAMPLE_EVERY:
                        req.t_put = critical_path.clock()
                    req.out_queue.put(tok)  # raylint: disable=R2 -- per-request stream queues are unbounded, so put() cannot block; token delivery and slot-state mutation must share one hold or a racing admit could reuse the slot mid-block
                    kept += 1
                    self._lengths[slot] += 1
                    if self._finished(req, tok) or \
                            self._lengths[slot] >= self.max_seq - 1:
                        self._retire(slot)  # raylint: disable=R2 -- _retire only pushes the unbounded-queue end-of-stream sentinel and frees the slot; both must be atomic with the walk above
                        break
            discarded = next_host.size - kept
            self._totals["tokens_kept"] += kept
            self._totals["tokens_discarded"] += discarded
            self._totals["slot_steps_stale"] += stale
            for name, n in counted.items():
                self._totals[name] += n
            sp.set(kept=kept, discarded=discarded, stale=stale,
                   slot_steps=next_host.size, behind_wave=int(behind_wave),
                   **counted)
        self._tile(sp)
        self._record_hand_over(behind_wave)

    def _record_hand_over(self, behind_wave):
        now = critical_path.clock()
        if self._handed_over is not None:
            total, name = ("blocks_behind_wave", "engine.block_gap.wave") \
                if behind_wave else ("blocks_plain", "engine.block_gap.plain")
            self._totals[total] += 1
            critical_path.record_stage(None, name, now - self._handed_over)
        self._handed_over = now

    def _deliver_first_tokens(self):
        """The last wave's first tokens to their clients: the wait for
        the wave's prefills (queued behind the block that was in flight
        at the wave), then for each admitted request its token, its
        `llm.prefill` stage (from its dispatch to the token on the
        host) and `llm.first_token` stage (from there to the client's
        queue). A request that ends on its first token is retired here;
        the block already dispatched for its slot is dropped by
        `_consume_block`'s owners."""
        pending, self._first_tokens = self._first_tokens, None
        if pending is None:
            return
        firsts_dev, staged = pending
        with critical_path.span("engine.token_fetch",
                                first_tokens=1) as fetch:
            firsts = np.asarray(firsts_dev)
            t_host = critical_path.clock()
            with critical_path.span("engine.first_tokens",
                                    admitted=len(staged)) as sp:
                ended = 0
                for (req, slot), first in zip(staged, firsts):
                    first = int(first)
                    critical_path.record_stage(
                        req.trace_id, "llm.prefill",
                        t_host - req.t_kv_done)
                    req.t_first_token = critical_path.clock()
                    critical_path.record_stage(
                        req.trace_id, "llm.first_token",
                        req.t_first_token - t_host)
                    req.tokens.append(first)
                    req.out_queue.put(first)
                    if self._finished(req, first):
                        self._retire(slot)
                        ended += 1
                self._totals["tokens_kept"] += len(staged)
                sp.set(ended=ended)
        self._tile(sp)
        self._pass_waited += fetch.dur_s - sp.dur_s  # the wait alone

    def _finished(self, req: _Request, token: int) -> bool:
        if token in req.params.stop_token_ids:
            return True
        return len(req.tokens) >= req.params.max_tokens

    def _retire(self, slot: int):
        req = self._slot_req.pop(slot, None)
        if req is not None:
            if req.t_first_token is not None:
                # Per-slot decode stage: first token → end of stream.
                critical_path.record_stage(
                    req.trace_id, "llm.decode",
                    critical_path.clock() - req.t_first_token)
            req.out_queue.put(None)
        self._active[slot] = False
        self._lengths[slot] = 0
        self._free_slots.append(slot)

    # -- prefix/KV cache ------------------------------------------------
    #
    # The PrefixCache core (pure, spec-checked) decides which blocks
    # exist; the engine owns the PAYLOADS: `_kv_store` maps block
    # generation id → host arrays, one a leaf of the cache in the
    # leaves' order (Llama's (k, v)), and evicted payloads fall to
    # the shm plane under a deterministic ObjectID derived from the
    # chain key. A chain key commits to the model seed + every token of
    # the prefix, so a key hit on ANY tier is byte-identical KV by
    # construction (same weights + same tokens + causal attention).
    #
    # A payload reaches `_kv_store` in two halves. The wave that
    # admitted the request creates its blocks in the core and only
    # DISPATCHES: one gather of the rows that hold them and an
    # asynchronous copy to the host (`_start_readback`). The loop
    # finishes it where the host would otherwise wait for a decode
    # block (`_finish_readbacks`): cut into per-block payloads, stored.
    # A block exists for lookups from its admission on, as before; a
    # payload needed while still on its way (a hit, an eviction to the
    # shm tier, the cap on pending bytes, stop, a model swap) is waited
    # for then and there (`_force_readbacks`) and counted.

    def _prefix_copy_in(self, req: _Request, slot: int, prompt):
        """Copy the longest cached prefix of `prompt` into `slot`'s KV
        region. Returns (matched_tokens, chain_keys)."""
        pc = self.prefix_cache
        if pc is None:
            return 0, []
        chain = chain_keys(prompt, self.block_tokens, self._chain_seed)
        if not chain:
            return 0, []
        hit = pc.lookup(chain, req.job)
        # Cap the match: (a) ≥1 real token must go through prefill (the
        # last-position logits feed the first sampled token), and (b)
        # matched_offset + tail_bucket must FIT the slot's KV region —
        # an overhanging padded bucket would clamp its KV write and
        # corrupt the copied prefix.
        m = min(len(hit), (len(prompt) - 1) // self.block_tokens)
        while m > 0:
            t_tail = len(prompt) - m * self.block_tokens
            if m * self.block_tokens + self._serve_bucket(t_tail) \
                    <= self.max_seq:
                break
            m -= 1
        while len(hit) > m:
            pc.release([hit.pop()])
        # Resolve payloads hot→warm; the first miss truncates the match
        # (a child block without its parent is useless).
        payloads = []
        for i, h in enumerate(hit):
            p = self._kv_store.get(h.block_id)
            rb = self._readback_of.get(h.block_id)
            if p is None and rb is not None:
                # Admitted a moment ago, still on its way to the host.
                self._force_readbacks(rb)
                p = self._kv_store.get(h.block_id)
            if p is None:
                p = self._shm_restore(h)
            if p is None:
                pc.release(hit[i:])
                hit = hit[:i]
                break
            payloads.append(p)
        for h, block in zip(hit, payloads):
            self.cache = self._write_block_j(
                self.cache, tuple(block), np.int32(slot),
                np.int32(h.index * self.block_tokens))
        pc.release(hit)
        return len(hit) * self.block_tokens, chain

    def _prefix_admit(self, req: _Request, slot: int, chain):
        """After the wave's sample program is dispatched (the first
        token never waits for it), admit the prompt's full-block chain
        and start the read-back of the blocks that created. The created blocks are pinned for just
        that long, and what the admission evicted leaves the host store
        at once, so the core sees the holds and the order it saw when
        the read-back was finished here."""
        pc = self.prefix_cache
        if pc is None or not chain:
            return
        created, evicted = pc.admit(chain, req.job, self._block_nbytes)
        if created:
            self._start_readback(slot, created)
        pc.release(created)
        self._offload_evicted(evicted)

    def _start_readback(self, slot: int, created):
        """The wave's half of a read-back, dispatch only: ONE gather of
        the slot's rows that hold `created` (ascending; at the prefill
        bucket that fits them, moved down where it would overhang the
        slot) and the start of its copy to the host. No wait, unless
        the pending arrays stand at their cap: then for the oldest."""
        bt = self.block_tokens
        lo, hi = created[0].index * bt, (created[-1].index + 1) * bt
        rows = self._serve_bucket(hi - lo)
        start = min(lo, self.max_seq - rows)
        nbytes = rows * self._block_nbytes // bt
        while self._readbacks \
                and self._readback_bytes + nbytes > self._readback_cap:
            self._force_readbacks(self._readbacks[0])
        blocks = len(created)
        with critical_path.span("engine.prefix_readback", blocks=blocks,
                                bytes=blocks * self._block_nbytes):
            arrays = self._run_read_rows(slot, start, rows)
            for a in arrays:
                a.copy_to_host_async()
            rb = _Readback(arrays, start, rows,
                           {h.block_id: h for h in created}, nbytes)
            with self._readback_lock:
                self._readbacks.append(rb)
                self._readback_of.update(dict.fromkeys(rb.handles, rb))
                self._readback_bytes += nbytes
        self._totals["kv_blocks_read_back"] += blocks
        self._totals["kv_bytes_read_back"] += blocks * self._block_nbytes
        self._totals["kv_readbacks_deferred"] += 1

    @staticmethod
    def _readback_ready(rb: _Readback) -> bool:
        """The gather has run (its copy to the host started behind it)."""
        return all(a.is_ready() for a in rb.arrays)

    def _complete_readback(self) -> int:
        """The host half of the oldest read-back: its blocks' rows
        copied out of the gathered arrays into one block-major slab a
        leaf of the cache (one copy a part, not one a block and leaf:
        each call that lets go of the interpreter may wait for it
        again), of which `_kv_store` holds a block's [layers_i, B, ...]
        views, a tuple in the leaves' order; they live until the last
        of the request's blocks is evicted. Blocks
        evicted meanwhile are left out. Caller holds `_readback_lock`.
        Returns the blocks stored."""
        rb = self._readbacks.popleft()
        self._readback_bytes -= rb.nbytes
        if not rb.handles:
            return 0
        arrays = [np.asarray(a) for a in rb.arrays]
        bt = self.block_tokens
        first = min(h.index for h in rb.handles.values())
        n = max(h.index for h in rb.handles.values()) + 1 - first
        lo = first * bt - rb.start
        slabs, at = [], 0
        for x in self._leaves:
            parts = arrays[at:at + self._layer_parts(x, rb.rows)]
            at += len(parts)
            tail = parts[0].shape[2:]
            slab = np.empty((n, x.shape[0], bt) + tail, parts[0].dtype)
            layer = 0
            for part in parts:
                nl = part.shape[0]
                slab[:, layer:layer + nl] = part[:, lo:lo + n * bt].reshape(
                    (nl, n, bt) + tail).swapaxes(0, 1)
                layer += nl
            slabs.append(slab)
        for block_id, h in rb.handles.items():
            self._kv_store[block_id] = tuple(
                slab[h.index - first] for slab in slabs)
            del self._readback_of[block_id]
        return len(rb.handles)

    def _finish_readbacks(self, max_blocks: Optional[int] = None,
                          block=None):
        """Finish, oldest first, the read-backs whose gather has run —
        never a wait for the device's queue. Called where the host has
        nothing else to do: behind a decode dispatch, where it stops
        after `max_blocks` blocks or as soon as the decode block that
        is fetched next (`block`) is ready, and in the idle loop."""
        if not self._readbacks:
            return
        with self._readback_lock:
            sp, blocks = None, 0
            while self._readbacks \
                    and (max_blocks is None or blocks < max_blocks) \
                    and self._readback_ready(self._readbacks[0]) \
                    and not (block is not None and block.is_ready()):
                if sp is None:
                    sp = critical_path.begin("engine.prefix_readback")
                blocks += self._complete_readback()
            if sp is not None:
                critical_path.end(sp, blocks=blocks,
                                  bytes=blocks * self._block_nbytes)

    def _force_readbacks(self, upto: Optional[_Readback] = None):
        """Wait for the pending read-backs, oldest first, through
        `upto` (all of them when None), and count them as forced."""
        with self._readback_lock:
            if not self._readbacks:
                return
            with critical_path.span("engine.prefix_readback") as sp:
                forced = blocks = 0
                while self._readbacks:
                    last = self._readbacks[0] is upto
                    blocks += self._complete_readback()
                    forced += 1
                    if last:
                        break
                self._totals["kv_readbacks_forced"] += forced
                sp.set(blocks=blocks, bytes=blocks * self._block_nbytes,
                       forced=forced)

    @staticmethod
    def _shm_object_id(key: str):
        from ray_tpu._private.ids import ObjectID
        return ObjectID(hashlib.blake2b(
            ("llmkv|" + key).encode(), digest_size=ObjectID.SIZE).digest())

    def _shm_plane(self):
        if not ray_config.llm_prefix_shm_tier:
            return None
        try:
            from ray_tpu._private.worker import global_worker_or_none
            w = global_worker_or_none()
            return getattr(w, "shm_plane", None)
        except Exception:
            return None

    def _shm_restore(self, handle):
        """Warm-tier fetch: a block evicted here (or admitted by ANOTHER
        replica — keys are content-addressed) comes back through the
        object plane instead of being recomputed."""
        plane = self._shm_plane()
        if plane is None:
            return None
        try:
            ok, payload = plane.get(self._shm_object_id(handle.key))
        except Exception:
            return None
        if not ok or payload is None:
            return None
        self._kv_store[handle.block_id] = payload
        self._c_shm_restores.inc()
        return payload

    def _offload_evicted(self, evicted):
        """Evicted blocks leave the host store but persist as shm-plane
        objects (spill-backed, charged to the admitting tenant's plane
        quota) — a later hit restores bytes instead of recomputing."""
        if not evicted:
            return
        plane, totals = self._shm_plane(), self._totals
        totals["kv_blocks_evicted"] += len(evicted)
        waits = _SHM_BACKPRESSURE_WAITS.value
        for e in evicted:
            rb = self._readback_of.get(e.block_id)
            if rb is not None and plane is not None:
                self._force_readbacks(rb)  # its bytes go to the warm tier
            elif rb is not None:
                with self._readback_lock:  # nobody wants them any more
                    del rb.handles[e.block_id]
                    del self._readback_of[e.block_id]
            payload = self._kv_store.pop(e.block_id, None)
            if plane is None or payload is None:
                continue
            t_put = critical_path.clock()
            try:
                if plane.maybe_put(self._shm_object_id(e.key), payload,
                                   timeout=0.1):
                    self._c_shm_offloads.inc()
                    totals["kv_blocks_offloaded"] += 1
            except Exception:
                pass  # warm tier is best-effort; the cold path recomputes
            totals["kv_offload_us"] += round(
                (critical_path.clock() - t_put) * 1e6)
        # Each is a full arena that `maybe_put` slept 10 ms on.
        totals["kv_offload_backpressure_waits"] += \
            _SHM_BACKPRESSURE_WAITS.value - waits

    # -- multi-model ----------------------------------------------------

    def swap_params(self, params, model: str):
        """Swap the served weight set (multi-model multiplexing). The
        compiled programs take params as ARGUMENTS with unchanged avals,
        so no recompile happens — the swap is one device_put. Caller
        must have drained the engine (no active slots / queued work):
        in-flight KV belongs to the OLD model, and what is still on its
        way to the host store gets there first."""
        self._force_readbacks()
        with self._lock:
            if self._active.any() or not self._queue.empty():
                raise RuntimeError(
                    "swap_params on a non-idle engine: drain first")
            self.params = jax.device_put(params, self._s1)
            self.model = model
            self._chain_seed = self._seed_for(model)

    def prefix_digests(self) -> Optional[Dict[str, Any]]:
        """Hot prefix-head digests for cache-affinity routing (exported
        through the serve membership channel). None ⇒ no hints (router
        falls back to least-loaded/round-robin)."""
        if self.prefix_cache is None or not ray_config.llm_affinity_routing:
            return None
        return {
            "model": self.model,
            "block_tokens": self.block_tokens,
            "seed": self._chain_seed,
            "block_bytes": self._block_nbytes,
            "keys": self.prefix_cache.hot_digests(
                int(ray_config.llm_digest_blocks)),
        }


class _BlockEngine(LLMEngine):
    """The engine of a model that generates by blocks of B positions
    (`ServedModel.block_length`; `models/serving.py` has the model's
    side of the contract). The loop, admission's order, the prefix
    cache, the pipelined fetch and every span's name are `LLMEngine`'s;
    what differs is what a step is.

    A slot carries, on the device, its block ([B] tokens and [B] flags,
    which positions are still open: never found by comparing ids with
    the mask id, a prompt may hold any id), the step of the block it is
    at, the step at which each position was fixed, its length, the
    committed rows, a multiple of B, and the block before, whole, with
    a flag that says whether it still awaits its commit. One
    fixed-shape program (`_decode_impl`, `decode_steps` forwards a
    dispatch) feeds every slot two blocks a forward, a start each
    (`models/serving.py`): the block that awaits its commit at the
    slot's length and the slot's own block behind it, the mask token
    at the open positions; or, where nothing awaits a commit, the
    slot's own block twice at the slot's length. The head runs on the
    second alone. Every forward *denoises* the slot's block: at every
    open position the token x0 (greedy at temperature 0, else drawn
    from softmax(logits / T)) and its confidence (x0's probability,
    float32), and the `n` most confident open positions, or those that
    are left, take their tokens (ties to the lower position; n = B /
    `SamplingParams.denoising_steps`, the config's unless the request
    says); if none is open now the block is *emitted*: it awaits its
    commit, and the next block opens behind it, all mask. The forward
    after that *commits* it while it fixes the next block's first
    positions: the emitted block's rows are written from its final
    tokens before the next block's queries read them, they stay, and
    the length grows by B. So a block of B positions costs
    `denoising_steps` forwards and no more, and a step hands the host,
    a slot, a row of B tokens, the steps they were fixed at, a count
    that is 0 or B, and whether it committed; `top_k` is not applied.

    Admission prefills the prompt's whole blocks, `(len // B) * B`
    tokens, at the engine's buckets (a bucket's padding lies in later
    blocks, which no real row sees), yields no first token, and seeds
    the slot's block with the `len % B` tokens left over as known
    positions (`_seed_blocks_impl`, the wave's one program in the
    sample program's place). The first tokens a client sees are its
    first block's, `denoising_steps` forwards after the prefill; a
    request ends inside a block (`max_tokens`, a stop id) and the rest
    of the block is dropped.

    The host counts by blocks: `_lengths` grows by B at a commit, and
    a forward of a request's slot is counted by what it did
    (`slot_forwards_denoise`: it fixed a position; `slot_forwards_fused`:
    it also committed the block before; `slot_forwards_commit`: it
    committed and fixed nothing, which the schedule above never does);
    `tokens_kept` are the tokens handed to requests and
    `tokens_discarded` the other token places of the emitted rows (a
    retired slot's, a first block's known positions, what follows a
    request's end), so that kept over `slot_steps` stays the share of
    what the device made that a request got."""

    def _make_step_programs(self, s1):
        b, n, cfg = self._step_len, self.n_slots, self.cfg
        assert self.max_seq % b == 0 and not b % cfg.denoising_steps, \
            (self.max_seq, b, cfg.denoising_steps)
        # A cached block of rows has to be whole blocks of the model:
        # a block's keys depend on nothing after the block.
        assert int(ray_config.llm_kv_block_tokens) % b == 0, \
            (ray_config.llm_kv_block_tokens, b)
        self._dev_block = jax.device_put(jnp.zeros((n, b), jnp.int32), s1)
        self._dev_open = jax.device_put(jnp.ones((n, b), bool), s1)
        self._dev_fixed_at = jax.device_put(jnp.zeros((n, b), jnp.int32), s1)
        self._dev_step = jax.device_put(jnp.zeros(n, jnp.int32), s1)
        self._dev_whole = jax.device_put(jnp.zeros((n, b), jnp.int32), s1)
        self._dev_awaits = jax.device_put(jnp.zeros(n, bool), s1)
        # Positions a denoising step fixes, a slot.
        self._nfix_arr = np.full(n, b // cfg.denoising_steps, np.int32)
        self._decode = jax.jit(
            self._decode_impl, donate_argnums=(1,),
            in_shardings=(None,) + (s1,) * 11, out_shardings=(s1,) * 11)
        # The wave's program: the admitted slots' blocks and lengths
        # into the carries, at one fixed shape.
        self._sample_admitted = jax.jit(
            self._seed_blocks_impl, in_shardings=(s1,) * 11,
            out_shardings=(s1,) * 7)
        self._totals.update(dict.fromkeys((
            "slot_forwards_denoise", "slot_forwards_commit",
            "slot_forwards_fused", "tokens_fixed", "blocks_emitted"), 0))

    def _carry_avals(self, aval):
        n, b = self.n_slots, self._step_len
        return (aval((n, b)), aval((n, b), jnp.bool_), aval((n, b)),
                aval((n,)), aval((n,)), aval((n, b)), aval((n,), jnp.bool_))

    def _decode_avals(self, aval):
        n = self.n_slots
        return (*self._carry_avals(aval), aval((n,), jnp.float32),
                aval((n,)))

    def _sample_avals(self, aval, rng_aval):
        n, b = self.n_slots, self._step_len
        return (*self._carry_avals(aval), aval((n,)), aval((n, b)),
                aval((n, b), jnp.bool_), aval((n,)))

    def _carries(self):
        return (self._dev_block, self._dev_open, self._dev_fixed_at,
                self._dev_step, self._dev_lengths, self._dev_whole,
                self._dev_awaits)

    def _set_carries(self, carries):
        (self._dev_block, self._dev_open, self._dev_fixed_at, self._dev_step,
         self._dev_lengths, self._dev_whole, self._dev_awaits) = carries

    def _run_seed(self, slots, blocks, opens, lengths):
        fn = self._sample_exec or self._sample_admitted
        return fn(*self._carries(), slots, blocks, opens, lengths)

    def _run_decode(self):
        fn = self._decode_exec or self._decode
        return fn(self.params, self.cache, *self._carries(),
                  jnp.asarray(self._temps_arr), jnp.asarray(self._nfix_arr),
                  self._rng)

    def _warm_step_programs(self, last):
        n, b = self.n_slots, self._step_len
        self._temps_arr = np.zeros(n, np.float32)
        self._run_seed(np.full(n, n, np.int32), np.zeros((n, b), np.int32),
                       np.ones((n, b), bool), np.zeros(n, np.int32))
        self.cache, out, *_carries, self._rng, _counts = self._run_decode()
        return out

    # -- compiled bodies -------------------------------------------------

    def _prefill_impl(self, params, cache, tokens, slot, length, start, t):
        """`LLMEngine._prefill_impl` of whole blocks: the slot's rows
        are written and no logits are handed on, so the program
        computes no head: a prefill yields no token."""
        cache, _ = super()._prefill_impl(params, cache, tokens, slot, length,
                                         start, t)
        return cache, jnp.zeros((), jnp.float32)

    def _seed_blocks_impl(self, block, open_, fixed_at, step, lengths,
                          whole, awaits, slots, new_block, new_open,
                          new_lengths):
        """A wave's rows, one an admitted request, into the carries at
        the row's slot: the first block (the prompt's leftover tokens,
        known, then open positions), the prefilled length, and no block
        that awaits its commit. Rows beyond the admitted count are
        padding: their slot is `n_slots`, which the update drops."""
        return (block.at[slots].set(new_block, mode="drop"),
                open_.at[slots].set(new_open, mode="drop"),
                fixed_at.at[slots].set(-1, mode="drop"),
                step.at[slots].set(0, mode="drop"),
                lengths.at[slots].set(new_lengths, mode="drop"),
                whole, awaits.at[slots].set(False, mode="drop"))

    def _decode_impl(self, params, cache, block, open_, fixed_at, step,
                     lengths, whole, awaits, temps, n_fix, rng):
        """`decode_steps` forwards a dispatch, by an in-program
        `lax.scan`, each of two blocks a slot (the class's docstring):
        the whole block that awaits its commit, or the slot's own block
        once more, and the block being denoised. Returns, after the
        cache, int32 [slots, K, 2 B + 3]: a forward's B tokens, the B
        steps they were fixed at, whether the block was emitted by this
        forward, whether the forward committed the block before it, and
        how many positions it fixed; then the carries, the rng and what
        the model counted over the K forwards."""
        b, mask_id = self._step_len, self.cfg.mask_token_id
        before = jnp.arange(b)[None, :] < jnp.arange(b)[:, None]  # [i, j]

        def forward(carry, _):
            cache, block, open_, fixed_at, step, lengths, whole, awaits, \
                rng = carry
            # Clamp for retired slots that keep computing until their
            # slot is re-admitted: their writes stay in the region. (A
            # request's slot has room for both blocks: `_consume_block`
            # retires it where the next block's rows would not fit.)
            lengths = jnp.minimum(lengths, self.max_seq - b)
            fed = jnp.where(open_, mask_id, block)
            starts = jnp.stack([lengths, jnp.minimum(
                lengths + jnp.where(awaits, b, 0), self.max_seq - b)], -1)
            logits, cache, counts = self._served.forward(
                params, jnp.concatenate(
                    [jnp.where(awaits[:, None], whole, fed), fed], -1),
                self.cfg, cache, starts, None)
            counts = tuple(counts[name] for name in self._count_names)
            # [slots, B, vocab], the block being denoised, at the
            # slot's temperature (as they are at temperature 0).
            logits = logits.astype(jnp.float32) / jnp.where(
                temps > 0, temps, 1.0)[:, None, None]
            rng, sub = jax.random.split(rng)
            greedy = logits.argmax(-1)
            x0 = jax.lax.cond(
                (temps > 0).any(),
                lambda: jnp.where(temps[:, None] > 0,
                                  jax.random.categorical(sub, logits),
                                  greedy),
                lambda: greedy).astype(jnp.int32)
            confidence = jnp.exp(
                jnp.take_along_axis(logits, x0[..., None], -1)[..., 0]
                - jax.nn.logsumexp(logits, -1))
            # How many positions stand ahead of position i: the open
            # ones of higher confidence, or of the same and before it.
            score = jnp.where(open_, confidence, -1.0)
            ahead = ((score[:, None, :] > score[:, :, None])
                     | ((score[:, None, :] == score[:, :, None]) & before)
                     ).sum(-1)
            fix = open_ & (ahead < n_fix[:, None])
            block = jnp.where(fix, x0, block)
            open_ = open_ & ~fix
            fixed_at = jnp.where(fix, step[:, None], fixed_at)
            emitted = ~open_.any(-1)
            out = jnp.concatenate(
                [block, fixed_at, emitted[:, None], awaits[:, None],
                 fix.sum(-1, keepdims=True)], -1, dtype=jnp.int32)
            # An emitted block awaits its commit and the next one opens
            # behind it, all mask.
            return (cache, block, open_ | emitted[:, None], fixed_at,
                    jnp.where(emitted, 0, step + 1),
                    lengths + jnp.where(awaits, b, 0),
                    jnp.where(emitted[:, None], block, whole), emitted,
                    rng), (out, counts)

        (cache, *carries, rng), (out, counts) = jax.lax.scan(
            forward, (cache, block, open_, fixed_at, step, lengths, whole,
                      awaits, rng), None, length=self.decode_steps)
        if counts:
            counts = jnp.stack(counts, -1).sum(0, dtype=jnp.int32)
        return (cache, out.transpose(1, 0, 2), *carries, rng, counts)

    # -- the loop's seams ------------------------------------------------

    def _known_tokens(self, prompt, params) -> int:
        steps = params.denoising_steps
        if steps is not None and (steps < 1 or self._step_len % steps):
            raise ValueError(
                f"denoising_steps {steps} does not divide the block's "
                f"length {self._step_len}")
        return len(prompt) % self._step_len

    def _dispatch_first_tokens(self, staged):
        """The wave's one program: each admitted slot's first block and
        prefilled length into the decode carries, queued behind the
        prefills. No token comes of it."""
        with critical_path.span("engine.sample_dispatch"):
            n, b = self.n_slots, self._step_len
            slots = np.full(n, n, np.int32)
            blocks = np.zeros((n, b), np.int32)
            opens = np.ones((n, b), bool)
            lengths = np.zeros(n, np.int32)
            for i, (req, slot, _logits, _chain) in enumerate(staged):
                slots[i] = slot
                known = req.known
                blocks[i, :known] = req.prompt[len(req.prompt) - known:]
                opens[i, :known] = False
                lengths[i] = len(req.prompt) - known
                self._nfix_arr[slot] = b // (req.params.denoising_steps
                                             or self.cfg.denoising_steps)
            self._set_carries(self._run_seed(slots, blocks, opens, lengths))

    def _dispatch_decode(self):
        self.cache, out, *carries, self._rng, counts = self._run_decode()
        self._set_carries(carries)
        return out, counts

    def _consume_block(self, next_host, owners, counts=(),
                       behind_wave=False):
        """`LLMEngine._consume_block` of rows of 0 or B tokens: every
        forward of an owner's slot is counted by what it did, an
        emitted block's tokens go to the request together, each with
        the step it was fixed at, and a commit grows the slot's length. The
        first block's known positions are the prompt's, and a request
        that ends inside a block leaves the rest. A request's first
        block closes its `llm.prefill` stage; from one block of a
        request to its next, one in four is recorded as
        `engine.emitted_block_gap`, the gap its client is dealt."""
        b = self._step_len
        tokens, fixed_at = next_host[..., :b], next_host[..., b:2 * b]
        emitted, committed, n_fixed = (next_host[..., 2 * b + i]
                                       for i in range(3))
        kept = stale = forwards = denoise = commit = fused = fixed = blocks = 0
        counted = dict(zip(self._count_names, map(int, np.asarray(counts))))
        with critical_path.span("engine.consume_block") as sp, self._lock:
            for slot in np.nonzero(self._active)[0]:
                req = self._slot_req[slot]
                if owners[slot] is not req:
                    stale += b * int(emitted[slot].sum())
                    continue
                for k in range(next_host.shape[1]):
                    forwards += 1
                    fixes = int(n_fixed[slot, k])
                    fixed += fixes
                    denoise += fixes > 0
                    if committed[slot, k]:
                        self._lengths[slot] += b
                        fused += fixes > 0
                        commit += not fixes
                    if not emitted[slot, k]:
                        continue
                    blocks += 1
                    now = critical_path.clock()
                    if req.t_first_token is None:
                        critical_path.record_stage(
                            req.trace_id, "llm.prefill",
                            now - req.t_kv_done)
                        req.t_first_token = now
                    elif not req.blocks % 4:
                        critical_path.record_stage(
                            None, "engine.emitted_block_gap", now - req.t_block)
                    req.blocks += 1
                    req.t_block = now
                    ended = False
                    for at in range(req.known, b):
                        tok = int(tokens[slot, k, at])
                        req.tokens.append(tok)
                        if not len(req.tokens) % LAG_SAMPLE_EVERY:
                            req.t_put = critical_path.clock()
                        req.out_queue.put((tok, int(fixed_at[slot, k, at])))
                        kept += 1
                        if self._finished(req, tok):
                            ended = True
                            break
                    req.known = 0
                    # The next block needs its B rows behind this one's.
                    if ended or self._lengths[slot] + 2 * b > self.max_seq:
                        self._retire(slot)
                        break
            slot_steps = b * int(emitted.sum())
            totals = self._totals
            totals["tokens_kept"] += kept
            totals["tokens_discarded"] += slot_steps - kept
            totals["slot_steps_stale"] += stale
            totals["slot_forwards_denoise"] += denoise
            totals["slot_forwards_commit"] += commit
            totals["slot_forwards_fused"] += fused
            totals["tokens_fixed"] += fixed
            totals["blocks_emitted"] += blocks
            for name, n in counted.items():
                totals[name] += n
            sp.set(kept=kept, discarded=slot_steps - kept, stale=stale,
                   slot_steps=slot_steps, behind_wave=int(behind_wave),
                   slot_forwards_denoise=denoise,
                   slot_forwards_commit=commit,
                   slot_forwards_fused=fused, slot_forwards=forwards,
                   tokens_fixed=fixed,
                   blocks_emitted=blocks, **counted)
        self._tile(sp)
        self._record_hand_over(behind_wave)


# -- Serve integration ------------------------------------------------------


# Priority classes understood on the wire (ints 0-2 also accepted).
_PRIORITY_CLASSES = {
    "high": 0, "interactive": 0, "normal": 1, "low": 2, "batch": 2,
}


def _parse_priority(raw) -> int:
    if isinstance(raw, str):
        return _PRIORITY_CLASSES.get(raw.lower().strip(), 1)
    try:
        return max(0, min(2, int(raw)))
    except (TypeError, ValueError):
        return 1


class LLMDeployment:
    """Deployment-ready wrapper: `serve.deployment(LLMDeployment).bind(...)`.

    `cfg` is the config of any architecture `models.serving` names a
    cached forward pass for (`LlamaConfig`, `GlmDsaConfig`,
    `NemotronHConfig`, ...): nothing else of the deployment depends on
    which, but that a model with a state leaf in its cache has no
    prefix cache, so the router gets no digests to route by, and that
    a model that generates by blocks (`SdarMoeConfig`) hands a stream
    its tokens a block at a time, each event with the key `step` (the
    denoising step of its block at which the token was fixed), and
    takes `denoising_steps` from a request.
    Each replica owns one engine (one KV cache in its chip's HBM) and
    may multiplex N weight variants (``models={name: params_fn}``): the
    compiled programs take params as arguments, so switching models is
    a drain + ``device_put``, never a recompile. A swap is charged to
    the requesting tenant and bounded by the
    ``llm_model_swap_deadline_s`` cold-start SLA (post-hoc: the weights
    stay cached, so a deadline miss leaves the NEXT attempt warm).
    Serve's router spreads requests over replicas, preferring replicas
    whose prefix cache already holds the request's prompt head.
    """

    # What `serve.deployment` gives a replica's in-flight cap unless the
    # caller names one. A request beyond the slots waits in the engine's
    # queue, where priority decides who is admitted and from where its
    # stream tells the reader that it waits (`__call__`); held back at the
    # router by the generic 100 it waits for a replica instead and is
    # shed with a 503 after the proxy's 15 s, though the engine would
    # have served it (128 closed-loop clients on 64 slots lost 28
    # requests that way; PERF.md section 6, PR 34).
    max_concurrent_queries = 1024

    def __init__(self, cfg, params_fn: Callable[[], Any] = None,
                 max_batch_size: int = 8,
                 max_seq_len: Optional[int] = None,
                 decode_steps: int = 1,
                 warmup: bool = True,
                 warmup_max_prompt_len: Optional[int] = None,
                 models: Optional[Dict[str, Any]] = None,
                 default_model: Optional[str] = None):
        self.models: Dict[str, Any] = dict(models or {})
        if params_fn is not None and not self.models:
            self.models[default_model or "default"] = params_fn
        if not self.models:
            raise ValueError("LLMDeployment needs params_fn or models={...}")
        self.default_model = default_model or next(iter(self.models))
        if self.default_model not in self.models:
            raise UnknownModelError(self.default_model, self.models)
        self._loaded: Dict[str, Any] = {}
        self._swap_lock = threading.RLock()
        self._c_swaps = perf_stats.counter("llm_model_swaps")
        enable_persistent_cache()  # the loader may compile (seeded init)
        params = self._load_model(self.default_model, job="deploy")
        self.engine = LLMEngine(cfg, params, max_batch_size=max_batch_size,
                                max_seq_len=max_seq_len,
                                decode_steps=decode_steps,
                                model=self.default_model)
        # Deploy-time AOT: compile prefill buckets + decode BEFORE the
        # replica takes traffic, so the first request's TTFT is serving
        # latency, not XLA compile. With the persistent compilation
        # cache, re-deploys of the same config load instead.
        self.warmup_s = self.engine.warmup(warmup_max_prompt_len) \
            if warmup else 0.0
        self.engine.start()

    def __del__(self):
        """The replica is stopped (`Replica.prepare_for_shutdown`): the
        engine's loop ends with it, whatever it still held."""
        engine = getattr(self, "engine", None)
        if engine is not None:
            engine.stop()

    # -- model loading / swapping ---------------------------------------

    def _load_model(self, model: str, job: str):
        """Resolve a model's weights: host cache → shm-plane warm tier →
        loader callable. The load is charged to the requesting tenant
        via the swap-bytes counter (and the plane publish is
        quota-charged by the plane itself)."""
        cached = self._loaded.get(model)
        if cached is not None:
            return cached
        src = self.models[model]
        params = src() if callable(src) else src
        self._loaded[model] = params
        try:
            nbytes = sum(
                int(x.size) * int(x.dtype.itemsize)
                for x in jax.tree_util.tree_leaves(params)
                if hasattr(x, "size") and hasattr(x, "dtype"))
            perf_stats.counter(
                "llm_model_swap_bytes", {"job": job}).inc(nbytes)
        except Exception:
            pass
        return params

    def _ensure_model(self, model: str, job: str):
        """Make `model` the engine's live weight set. Caller holds
        `_swap_lock`, which also covers the subsequent enqueue — no
        other request can slip a different model in between. Returns
        the loaded params (unused by the engine path, handy for
        tests)."""
        if model not in self.models:
            raise UnknownModelError(model, self.models)
        if self.engine.model == model:
            return self._loaded.get(model)
        t0 = time.perf_counter()
        # Drain: every request enqueues under _swap_lock (held by us),
        # so active/queued can only fall.
        while True:
            m = self.engine.metrics()
            if m["active_slots"] == 0 and m["queued"] == 0:
                break
            time.sleep(0.002)
        params = self._load_model(model, job)
        self.engine.swap_params(params, model)
        self._c_swaps.inc()
        took = time.perf_counter() - t0
        deadline = float(ray_config.llm_model_swap_deadline_s or 0)
        if deadline and took > deadline:
            # Post-hoc SLA: the swap COMPLETED and the weights stay
            # cached, so the caller's retry is warm.
            raise ModelSwapDeadlineError(model, took, deadline)
        return params

    def prefix_digests(self):
        return self.engine.prefix_digests()

    def stats(self) -> Dict[str, Any]:
        """Engine metrics plus what the deploy cost (reachable through
        the handle: ``handle.stats.remote()``)."""
        return {"warmup_s": self.warmup_s, **self.engine.metrics()}

    def __call__(self, request: Dict[str, Any]):
        t0 = time.perf_counter()
        params = SamplingParams(
            max_tokens=int(request.get("max_tokens", 64)),
            temperature=float(request.get("temperature", 0.0)),
            stop_token_ids=tuple(request.get("stop_token_ids", ())),
            denoising_steps=request.get("denoising_steps"))
        model = str(request.get("model") or self.default_model)
        priority = _parse_priority(request.get("priority", 1))
        job = str(request.get("job") or request.get("job_id") or "default")
        # Hold the swap lock across ensure + enqueue: a concurrent
        # request for a DIFFERENT model must not swap weights between
        # our check and our admission. Token consumption happens
        # outside the lock — a queued request pins its model because
        # any later swap drains the queue first.
        with self._swap_lock:
            self._ensure_model(model, job)  # raylint: disable=R2 -- the blocking drain IS the design: the swap lock must span drain+swap+enqueue or a concurrent request could swap weights between our model check and our admission; the engine drains independently of this lock, so the wait always terminates
            it = self.engine.generate(
                request["prompt_ids"], params, stream=True,
                model=model, priority=priority, job=job, with_steps=True,
                beat_s=WAITING_BEAT_S if request.get("stream") else None)
        if request.get("stream"):
            # Generator return → the replica streams it chunk-by-chunk
            # (tokens reach the client during decode, not after). A
            # request the engine holds in its queue says so to the
            # stream's reader, which would else end it as hung after
            # its 60 s without a chunk (`serve/streaming.py`).
            def token_stream():
                i = 0
                for item in it:
                    if item is None:
                        yield {STREAM_WAITING_KEY: True}
                        continue
                    token, step = item
                    # A model that generates by blocks: its tokens come
                    # a block at a time, each with the denoising step
                    # of its block at which it was fixed.
                    yield {"token": int(token), "index": i,
                           **({} if step is None else {"step": step})}
                    i += 1
            return token_stream()
        tokens, steps = [], []
        ttft_s = None
        for token, step in it:
            if ttft_s is None:
                ttft_s = time.perf_counter() - t0
            tokens.append(int(token))
            steps.append(step)
        return {"tokens": tokens,
                **({"steps": steps} if tokens and steps[0] is not None
                   else {}),
                "model": model,
                "ttft_s": ttft_s,
                "latency_s": time.perf_counter() - t0}

    def check_health(self):
        assert self.engine._thread is None or \
            self.engine._thread.is_alive() or \
            not self.engine._running.is_set()
