"""The one way a configuration reaches the program's model: the adapter
`benchmark/models/<family>.py` that its `family` names. A family that
no file of the benchmark knows runs through the train runner as files
alone; nothing else under `benchmark/` names a model of the program;
the two adapters that exist build the objects the runners built before
them; a family that cannot be served says so at set-up."""

import ast
import dataclasses
import json
import os
import re
import sys
import types

import jax.numpy as jnp
import pytest

from benchmark.harness.manifest import (BENCH_DIR, ROOT, Cell, manifest,
                                        model_adapter)
from benchmark.runners import serve as serve_runner
from ray_tpu.models.llama import (LlamaConfig, init_params_sharded,
                                  loss_fn)
from ray_tpu.models.moe import MoEConfig
from tests.benchmark.test_runners import cut_train, drive

# Spelled so that no file under benchmark/ can hold it by accident.
TOY = "toy_tied_mha_v0"


def toy_adapter():
    """A family of the test's own: full multi-head attention through the
    program's reference attention path and a tied output head, two
    things no key of the `dense` family's files can ask for, read from
    keys of its own spelling."""
    toy = types.ModuleType(f"benchmark.models.{TOY}")

    def program_config(config):
        own = config["toy"]
        return LlamaConfig(
            vocab_size=config["vocab_size"], dim=own["width"],
            n_layers=own["depth"], n_heads=own["heads"],
            n_kv_heads=own["heads"], hidden_dim=own["ffn"],
            max_seq_len=own["context"], rope_theta=own["rope_base"],
            norm_eps=own["eps"], tie_embeddings=True, dtype=jnp.bfloat16,
            attention=own["attention_path"])

    toy.program_config = program_config
    toy.with_remat = lambda cfg, policy: dataclasses.replace(
        cfg, remat=policy)
    toy.init_sharded = init_params_sharded
    toy.loss = loss_fn
    toy.debug = dict  # it is written at debug width
    return toy


def toy_config():
    return {
        "kind": "train", "family": TOY,
        "reference": "dense_decoder", "flops": "decoder",
        # What the reference and the FLOP count read, as config.json
        # spells it, ...
        "vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 4, "rope_theta": 1e4, "rms_norm_eps": 1e-5,
        "tie_word_embeddings": True,
        # ... and what only this family's adapter reads.
        "toy": {"width": 64, "depth": 2, "heads": 4, "ffn": 128,
                "context": 256, "rope_base": 1e4, "eps": 1e-5,
                "attention_path": "reference"},
        "train": {"sequences_per_chip": 2, "mesh": {"data": 1, "fsdp": 1},
                  "remat": False, "learning_rate": 3e-4, "blocks": 4,
                  "loss_tolerance": 1e-4}}


def test_a_family_of_files_alone_runs_through_the_train_runner(
        tmp_path, monkeypatch):
    monkeypatch.setitem(sys.modules, f"benchmark.models.{TOY}",
                        toy_adapter())
    path = tmp_path / "toy.json"
    path.write_text(json.dumps(toy_config()))
    bench = manifest()
    bench["configs"].append({"name": "toy", "file": str(path)})
    bench["workloads"].append({"name": "train-toy", "config": "toy",
                               "traffic": "pretrain-4k", "chips": 1})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "train-dense-1chip" in metric.get("workloads", []):
            metric["workloads"].append("train-toy")
    cell = Cell("train-toy", bench)
    cell.config = model_adapter(cell.config).debug(cell.config)
    cut_train(cell.config, cell.traffic)
    run, metrics = drive(cell, tmp_path, seconds=1.5)
    assert all(run["checks"].values()), run["log"]
    assert run["failed"] == 0 and run["attempted"] == run["steps"] >= 8
    assert metrics["end_to_end"]["train_tokens_per_s_per_chip"]["value"] > 0
    assert metrics["per_layer"]["step.mfu"]["value"] > 0
    # All of it without the family's name in any file of the benchmark.
    for folder, _, files in os.walk(BENCH_DIR):
        for name in files:
            with open(os.path.join(folder, name), "rb") as f:
                assert TOY.encode() not in f.read(), (folder, name)


PROGRAM_MODEL = re.compile(
    r"models\.(llama|moe)|LlamaConfig|MoEConfig|init_(moe_)?params|loss_fn"
    r"|forward_with_cache|init_kv_cache")


def code_of(path):
    """The file's code without its comments and docstrings."""
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        body = getattr(node, "body", None)
        if isinstance(body, list) and body and \
                isinstance(body[0], ast.Expr) and \
                isinstance(body[0].value, ast.Constant) and \
                isinstance(body[0].value.value, str):
            body[0] = ast.Pass()
    return ast.unparse(tree)


def test_only_the_adapters_name_a_model_of_the_program():
    adapters = os.path.join(BENCH_DIR, "models")
    seen = 0
    for folder, _, files in os.walk(BENCH_DIR):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(folder, name)
            hits = [line for line in code_of(path).splitlines()
                    if PROGRAM_MODEL.search(line)]
            if folder == adapters:
                seen += bool(hits)
            else:
                assert not hits, (os.path.relpath(path, ROOT), hits)
    assert seen >= 2  # the expression does find what it looks for
    # The normal path of every family stays where the measurement is.
    with open(os.path.join(BENCH_DIR, "runners", "train.py")) as f:
        assert "make_train_step" in f.read()
    with open(os.path.join(BENCH_DIR, "runners", "serve.py")) as f:
        assert "LLMDeployment" in f.read()


DECODER = dict(dim=4096, n_heads=32, n_kv_heads=8, hidden_dim=14336,
               max_seq_len=32768, rope_theta=1e6, norm_eps=1e-5,
               tie_embeddings=False, dtype=jnp.bfloat16, attention="auto",
               remat=True, fused_ce=True)
# What `model_config()` of the train runner gave for each file before
# the adapters (d180bee), field by field.
BEFORE = {
    "mistral-7b-v0.3-train": LlamaConfig(
        vocab_size=32768, n_layers=5, **DECODER),
    "mistral-7b-v0.3-serve": LlamaConfig(
        vocab_size=32768, n_layers=16, **DECODER),
    "mixtral-8x7b-v0.1-train": MoEConfig(
        vocab_size=32000, n_layers=2, n_experts=8, n_experts_per_token=2,
        aux_loss_coeff=0.02, **DECODER),
}


@pytest.mark.parametrize("name", BEFORE)
def test_the_adapters_build_the_objects_the_runners_built(name):
    files = {c["name"]: c["file"] for c in manifest()["configs"]}
    with open(os.path.join(ROOT, files[name])) as f:
        config = json.load(f)
    adapter = model_adapter(config)
    cfg = adapter.program_config(config)
    assert type(cfg) is type(BEFORE[name]) and cfg == BEFORE[name]
    # The two changes a runner makes to it, and nothing else moves.
    assert adapter.with_remat(cfg, "gate") == dataclasses.replace(
        cfg, remat="gate")
    if config["kind"] == "serve":
        assert adapter.with_layers(cfg, 2) == dataclasses.replace(
            cfg, n_layers=2)
    small = adapter.program_config(adapter.debug(config))
    assert (small.dim, small.n_layers, small.vocab_size) == (64, 2, 512)
    assert config["hidden_size"] == 4096  # `debug` cut a copy


def test_a_family_without_a_served_path_says_so_at_set_up(tmp_path):
    cell = Cell("serve-batch-closed")
    cell.config = {**cell.config, "family": "moe"}
    with pytest.raises(SystemExit) as refusal:
        serve_runner.run(cell, seed=1, seconds=1.0, trace_dir=None,
                         devices=[], run_dir=str(tmp_path))
    message = str(refusal.value)
    assert "'moe'" in message and "benchmark/models/moe.py" in message
    for piece in ("init", "cached_forward", "init_cache",
                  "deployment_args"):
        assert piece in message
    with pytest.raises(SystemExit, match="'serve'"):
        serve_runner.Deployment(cell, seed=1)  # `sweep.py`'s way in


def test_a_family_with_no_file_is_an_error_that_names_the_module():
    with pytest.raises(ModuleNotFoundError, match="benchmark.models.no_such"):
        model_adapter({"family": "no_such", "kind": "train"})
