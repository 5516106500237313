"""What `chip_smoke.py` establishes, as far as it can be checked without
a chip: its two phase functions at `LlamaConfig.debug()` sizes on the CPU
mesh, its refusal of a non-TPU backend, where the compile cache goes,
and that a process told its TPU count stays off JAX."""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import chip_smoke
import ray_tpu
from ray_tpu._private import compile_cache
from ray_tpu.models import LlamaConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _python(code, *, cwd=REPO, **env):
    base = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    return subprocess.run(
        [sys.executable, "-c", code], cwd=cwd, capture_output=True,
        text=True, timeout=120,
        env={**base, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu", **env})


def test_phases_at_debug_size_on_cpu_mesh():
    """Also the tier-1 LLM serving check: SSE and unary answers through
    the real proxy -> replica path, the shared prompt head served from
    the prefix cache, greedy decoding identical across the hit."""
    n = len(jax.devices())
    # bfloat16 as at real widths: the dtype prefill's logits reach the
    # AOT-compiled sampler in.
    cfg = dataclasses.replace(LlamaConfig.debug(), dtype=jnp.bfloat16)
    compiles = chip_smoke.CompileLog()
    ray_tpu.shutdown()
    # Told its TPUs: the virtual CPU devices stand in for the chips the
    # worker is granted.
    ray_tpu.init(num_cpus=4, num_tpus=n)
    try:
        train = chip_smoke.train_phase(cfg, batch=n, seq=32, steps=5,
                                       n_devices=n, compiles=compiles)
        serve = chip_smoke.serve_phase(
            cfg, max_batch_size=4, max_seq_len=128, prompt_lens=(3, 20),
            shared_head=64, max_tokens=4, compiles=compiles)
    finally:
        ray_tpu.shutdown()
    assert train["mesh"] == {"fsdp": n} and train["granted_tpus"] == n
    # Off the TPU "auto" is the reference: no Mosaic call in the program.
    assert train["attention_calls"] == {}
    assert train["compiled"]["programs"] > 0
    # Buckets 1..128, decode, sampler, and the prefix cache's read-back
    # gathers at the buckets that hold a 16-token block (16..128).
    assert serve["compiled_programs"] == 8 + 2 + 4
    assert serve["requests"] == 5 and serve["tokens"] == 20
    json.dumps({"train": train, "serve": serve})  # the summary line holds


def test_attention_calls_reads_mosaic_custom_calls():
    # Two lines as the v5e compiler prints them, cut to what is parsed.
    hlo = '''
  %fusion.1 = bf16[4,2048] fusion(%p0), kind=kLoop
  %flash_fwd.4 = (bf16[1,32,2048,64]{3,2,1,0:T(8,128)(2,1)S(1)}, f32[1,32,2048,128]{3,2,1,0:T(8,128)}) custom-call(%a, %b, %c), custom_call_target="tpu_custom_call", operand_layout_constraints={bf16[1,32,2048,64]{3,2,1,0}}, metadata={op_name="jit(step_fn)/jvp()/while/body/closed_call/flash_fwd/pallas_call" stack_frame_id=105}, backend_config={}
  %flash_bwd_dq.11 = bf16[1,32,2048,64]{3,2,1,0:T(8,128)(2,1)} custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_fn)/transpose(jvp())/while/body/closed_call/checkpoint/flash_bwd_dq/pallas_call" stack_frame_id=33}
'''
    assert chip_smoke.attention_calls(hlo) == {
        "flash_fwd": [["bf16[1,32,2048,64]", "f32[1,32,2048,128]"]],
        "flash_bwd_dq": [["bf16[1,32,2048,64]"]],
    }


def test_result_line_has_exactly_the_keys_the_driver_reads():
    devices = jax.devices()
    assert json.loads(chip_smoke.result_line(devices)) == {
        "ok": True, "device": {"platform": "cpu", "count": len(devices),
                               "kind": devices[0].device_kind}}


def test_refuses_a_backend_that_is_not_a_tpu(capsys):
    # What `JAX_PLATFORMS=cpu python chip_smoke.py` does: SystemExit with
    # a message is exit code 1 and the message on stderr.
    with pytest.raises(SystemExit, match="platform 'cpu'"):
        chip_smoke.main()
    assert capsys.readouterr().out == ""  # no result line


def test_compile_cache_placement(tmp_path, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    # The CPU backend is left uncached; report what a TPU process does.
    code = ("import jax; jax.default_backend = lambda: 'tpu'\n"
            "from ray_tpu._private.compile_cache import "
            "enable_persistent_cache\n"
            "print(enable_persistent_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)")
    # Placed from outside: JAX reads the variable (when it is imported),
    # the code sets no directory and makes none.
    outside = str(tmp_path / "elsewhere")
    with monkeypatch.context() as m:
        m.setenv("JAX_COMPILATION_CACHE_DIR", outside)
        m.setattr(jax, "default_backend", lambda: "tpu")
        before = jax.config.jax_compilation_cache_dir
        assert compile_cache.enable_persistent_cache() == outside
        assert jax.config.jax_compilation_cache_dir == before
        assert not os.path.exists(outside)
    # Not placed: one directory in the checkout, from this process's
    # working directory and from another.
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.cache_dir() == want
    proc = _python(code, cwd=str(tmp_path))
    assert proc.stdout.split() == [want, want], proc.stderr
    assert compile_cache.enable_persistent_cache() is None  # this CPU run


def test_init_told_its_tpus_stays_off_jax():
    proc = _python(
        "import sys, ray_tpu\n"
        "ray_tpu.init(num_cpus=1, num_tpus=0)\n"
        "print('jax' in sys.modules)\n"
        "ray_tpu.shutdown()")
    assert proc.stdout.split() == ["False"], proc.stderr


def test_tpu_work_is_refused_in_a_process_that_cannot_open_the_chip():
    """One process per chip: a forked worker never can, and a spawned
    one cannot once this process has counted (opened) the chips."""
    from ray_tpu._private.worker import global_worker

    @ray_tpu.remote(num_tpus=1, max_retries=0)
    def on_chip():
        return os.getpid()

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2, num_tpus=1)
    try:
        with pytest.raises(Exception, match="forked worker"):
            ray_tpu.get(on_chip.options(isolate_process=True).remote(),
                        timeout=30)
        global_worker().holds_chip = True  # as init() without num_tpus
        with pytest.raises(Exception, match="tell init\\(\\) its num_tpus"):
            ray_tpu.get(on_chip.options(isolate_process="spawn").remote(),
                        timeout=30)
    finally:
        ray_tpu.shutdown()
