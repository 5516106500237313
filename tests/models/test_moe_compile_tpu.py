"""The expert layer compiled for a described v5e at the training
configurations' real shapes, and the served shares' decode and prefill
programs at theirs, without the chip: the TPU's compiler
refuses here what it would refuse there (a grouped matmul it cannot
tile, a program it cannot fit). Nothing runs and no time
is read. The topology is described inside a fixture, never at import
(one process at a time may load the TPU's library)."""

import json
import math
import pathlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.models import moe

# The grouped product's names, kept in one place: the benchmark's
# reader of the kernel's roofline finds it in a trace by the same list.
PRODUCTS = json.loads((pathlib.Path(__file__).parents[2] / "benchmark"
                       / "metrics" / "kernel.grouped_matmul_roofline.json"
                       ).read_text())["args"]["products"]


@pytest.fixture(scope="module")
def topology():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topology):
    return SingleDeviceSharding(topology.devices[0])


@pytest.mark.parametrize("tokens,k,d,f,e", [
    (16384, 8, 2048, 1024, 64),   # OLMoE-1B-7B, 4 x 4096 tokens a chip
    (4096, 2, 4096, 14336, 8),    # Mixtral-8x7B, 4096 tokens a chip
], ids=["olmoe", "mixtral"])
def test_expert_layer_compiles_for_the_v5e(one_chip, tokens, k, d, f, e):
    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def loss(x, gates, top_i, we1, we3, we2):
        out = moe._sparse_experts(x, gates, top_i, we1, we3, we2)
        return out.astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 3, 4, 5))).lower(
        shape(tokens, d), shape(tokens, k, dtype=jnp.float32),
        shape(tokens, k, dtype=jnp.int32), shape(e, d, f), shape(e, d, f),
        shape(e, f, d)).compile()
    text = compiled.as_text()
    # Forward 3 grouped products, backward 6: each the compiler's
    # grouped-matmul kernel, not a dense product over every expert.
    kernels = re.findall(r"%([\w-]+?)(?:\.\d+)? = \S+ custom-call\(", text)
    assert sum(k in PRODUCTS for k in kernels) == 9
    # The permutations are row gathers in both directions: no scatter
    # of rows (the kernel's own group metadata scatters into vectors).
    assert not re.search(r"\[\d+,\d+\]\S* scatter\(", text)


def _train_step(name, devices, monkeypatch):
    """The whole train step of `benchmark/configs/<name>.json` as the
    train runner builds it (its mesh with `fsdp` over `devices`, its
    file's `remat`, bf16 moments, one batch of its sequences of 4096),
    compiled from shapes for the described chips: (cfg, compiled). The
    flash kernels would not be this process's CPU choice: the test says
    it is on a TPU."""
    import optax
    from jax.sharding import NamedSharding, PartitionSpec

    from benchmark.harness.manifest import ROOT, load_json, model_adapter
    from ray_tpu.models import llama, make_optimizer, make_train_step
    from ray_tpu.models.training import TrainState
    from ray_tpu.ops import attention
    from ray_tpu.parallel import MeshConfig, create_mesh, named_sharding
    from ray_tpu.parallel.sharding import tree_shardings

    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    monkeypatch.setattr(llama, "on_tpu", lambda: True)
    config = load_json(ROOT, "benchmark", "configs", name + ".json")
    plan = config["train"]
    model = model_adapter(config)
    cfg = model.with_remat(model.program_config(config), plan["remat"])
    assert cfg.remat is True and cfg.n_layers == 2
    mesh = create_mesh(MeshConfig(**{**plan["mesh"], "fsdp": len(devices)}),
                       devices=devices)
    whole = NamedSharding(mesh, PartitionSpec())

    def on_mesh(tree, shardings):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                              sharding=s or whole),
            tree, shardings, is_leaf=lambda x: x is None)

    params = on_mesh(
        jax.eval_shape(lambda: moe.init_moe_params(cfg,
                                                   jax.random.PRNGKey(0))),
        tree_shardings(mesh, moe.moe_param_logical_axes(cfg),
                       moe.DEFAULT_RULES))
    tx = make_optimizer(plan["learning_rate"], warmup_steps=0,
                        moment_dtype=jnp.bfloat16)
    moments = jax.eval_shape(tx.init, params)
    moments = on_mesh(moments, optax.tree_map_params(
        tx, lambda _, p: p.sharding, moments, params,
        transform_non_params=lambda _: None))
    state = TrainState(jax.ShapeDtypeStruct((), jnp.int32, sharding=whole),
                       params, moments)
    batch = {key: jax.ShapeDtypeStruct(
        (plan["sequences_per_chip"] * len(devices), 4096), jnp.int32,
        sharding=named_sharding(mesh, "batch", "seq"))
        for key in ("tokens", "targets")}
    step = make_train_step(
        lambda p, b: model.loss(p, b, cfg, mesh=mesh), tx, mesh=mesh,
        batch_logical={key: ("batch", "seq") for key in batch})
    return cfg, step.lower(state, batch).compile()


def test_olmoes_train_step_compiles_for_the_v5e(one_chip, monkeypatch):
    """The whole train step of `olmoe-1b-7b-0125-train.json` (2 layers,
    4 x 4096 tokens, bf16 moments, its file's `remat`) as the train
    runner builds it, on a mesh of the one described chip. A
    rematerialised layer keeps `moe._SAVED`: the program holds the
    layer's 3 grouped products forward and 6 backward, where keeping
    nothing made the backward scan's body compute the 3 again, and one
    forward attention kernel, in the forward scan alone. What that
    keeps is memory the cell has: the compiler's peak stays under nine
    tenths of the 15.75 GiB a v5e offers. On its one chip the expert
    layer moves nothing: the program holds no collective."""
    _, compiled = _train_step("olmoe-1b-7b-0125-train",
                              list(one_chip.device_set), monkeypatch)
    text = compiled.as_text()
    kernels = re.findall(r"%([\w-]+?)(?:\.\d+)? = \S+ custom-call\(", text)
    assert sum(k in PRODUCTS for k in kernels) == 9
    assert sorted(re.findall(
        r"%\w*?(flash_(?:fwd|bwd_dq|bwd_dkv))_*\.\d+ = .* custom-call\(",
        text)) == ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]
    assert not re.findall(r" (?:all-gather|all-reduce|reduce-scatter|"
                          r"all-to-all|collective-permute)\S*\(", text)
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes > 6.2e9  # parameters and moments
    assert memory.peak_memory_in_bytes < 0.9 * 15.75 * 2 ** 30


def test_mixtrals_train_step_on_four_chips_moves_no_expert_matrix(
        topology, monkeypatch):
    """The whole train step of `mixtral-8x7b-v0.1-train.json` (2 layers,
    one sequence of 4096 a chip, `fsdp=4`) compiled for all four
    devices of the described v5e:2x2. A chip owns its quarter of every
    expert's hidden width and is sent the tokens: the scan's body holds
    the layer's 3 grouped products forward and 6 backward, each one
    call over the four chips' 32,768 rows, and no collective's operand
    or result is the size of an expert matrix, whole (what the step
    gathered three times a layer and pass, 2.8 GB, and scattered the
    gradients of) or a chip's quarter."""
    cfg, compiled = _train_step("mixtral-8x7b-v0.1-train",
                                list(topology.devices), monkeypatch)
    text = compiled.as_text()
    kernels = re.findall(r"%([\w-]+?)(?:\.\d+)? = \S+ custom-call\(", text)
    assert sum(k in PRODUCTS for k in kernels) == 9
    matrix = cfg.n_experts * cfg.dim * cfg.hidden_dim
    moved = [math.prod(map(int, dims.split(",")))
             for line in text.splitlines()
             if re.search(r" (?:all-gather|reduce-scatter|all-reduce|"
                          r"all-to-all)(?:-start)?\(", line)
             for dims in re.findall(r"\w+\[([\d,]+)\]", line)]
    assert moved and not {matrix, matrix // 4} & set(moved)
    # The tokens are what crosses: every chip's rows in, and the sum of
    # the chips' partial outputs back in float32.
    rows = 4 * 4096
    assert re.search(rf"= bf16\[1,{rows},{cfg.dim}\]\S* all-gather\(", text)
    assert re.search(rf"= f32\[4096,{cfg.dim}\]\S* reduce-scatter\(", text)
    memory = compiled.memory_analysis()
    assert memory.peak_memory_in_bytes < 0.9 * 15.75 * 2 ** 30


def _scheduled(text):
    """A compiled program's text without the bodies of its fusions:
    the ops it schedules, each of which leaves an array in memory (a
    `copy` or a `dynamic-slice` inside a fusion is how that fusion
    reads its operand, and leaves none)."""
    fused = set(re.findall(r" fusion\(.*calls=%([\w.-]+)", text))
    kept, inside = [], False
    for line in text.splitlines():
        head = re.match(r"%([\w.-]+) \(", line)
        if head:
            inside = head.group(1) in fused
        if not inside:
            kept.append(line)
    return "\n".join(kept)


def _engines_program(one_chip, name, program, bucket, count_names,
                     compile=True):
    """The engine's own decode or prefill program for the configuration
    `benchmark/configs/<name>.json` at its cell's slots, compiled for
    the described chip from shapes alone: (cfg, params, cache,
    compiled), the first three as shapes; lowered and no more where
    not `compile`."""
    from benchmark.harness.manifest import ROOT, load_json, model_adapter
    from ray_tpu.models.serving import served_model
    from ray_tpu.serve.llm import LLMEngine

    config = load_json(ROOT, "benchmark", "configs", name + ".json")
    model = model_adapter(config)
    cfg = model.program_config(config)
    plan = config["serve"]
    n, rows = plan["max_batch_size"], plan["max_seq_len"]

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    def ints(*dims, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    params = on_chip(jax.eval_shape(
        lambda: model.init(cfg, jax.random.PRNGKey(0))))
    engine = LLMEngine.__new__(LLMEngine)  # its programs, no device
    engine.cfg, engine._served = cfg, served_model(cfg)
    cache = on_chip(jax.eval_shape(
        lambda: engine._served.init_cache(cfg, n, rows)))
    engine.max_seq, engine.decode_steps, engine.n_slots = rows, 1, n
    engine._count_names = count_names
    if program == "decode":
        lowered = jax.jit(engine._decode_impl, donate_argnums=(1,)).lower(
            params, cache, ints(n), ints(n), ints(n, dtype=jnp.float32),
            ints(n), ints(2, dtype=jnp.uint32))
    else:
        lowered = jax.jit(engine._prefill_impl, donate_argnums=(1,),
                          static_argnums=(6,)).lower(
            params, cache, ints(1, bucket), ints(), ints(), ints(), bucket)
    return cfg, params, cache, lowered.compile() if compile else lowered


def _reads_its_weights_where_they_lie(scheduled, text, calls, *leaves):
    """A decode step whose halves name leaves (`ops/stacked_product.py`;
    PERF.md, PR 60): `calls` `stacked_product` kernels in all, and no
    op that leaves in memory an array of one layer of a named leaf
    (`leaves`: a layer's shape each), which is what the layer scan's
    slice of it was (`constant_dynamic-slice_fusion`) and what laid a
    run of one layer's out anew (`copy`). The compiler may still fetch
    a leaf ahead into fast memory as it lies (`copy-start`,
    `slice-start`): that reads it once and writes no byte back."""
    kernels = re.findall(r"%stacked_product(?:\.\d+)? = \S+ custom-call\(",
                         text)
    assert len(kernels) == calls
    for leaf in leaves:
        dims = ",".join(map(str, leaf))
        assert not re.findall(
            rf"= bf16\[(?:1,)?{dims}\]\S* (?:fusion|copy|dynamic-slice|"
            r"transpose)\(", scheduled), leaf


@pytest.mark.parametrize("name,program,bucket,resident,products", [
    ("glm-5.2-serve", "decode", 0, 9.3e9, 3),
    ("glm-5.2-serve", "prefill", 8192, 9.3e9, 3),
    ("nemotron-3-super-serve", "decode", 0, 10.9e9, 2),
    ("nemotron-3-super-serve", "prefill", 2048, 10.9e9, 2),
    ("command-a-plus-serve", "decode", 0, 11.3e9, 3),
    ("command-a-plus-serve", "prefill", 12288, 11.3e9, 3),
])
def test_served_step_compiles_for_the_v5e(one_chip, name, program, bucket,
                                          resident, products, monkeypatch):
    """A served share as its file under `benchmark/configs/` cuts it,
    through the engine's own decode and prefill programs at its cell's
    slots and the cell's largest bucket: it compiles, the held experts
    run through their own grouped kernel (`ops/grouped_matmul.py`'s
    `gmm`, which this process's CPU backend would not choose: the test
    says it is on a TPU) where their matrices lie, and the program fits
    beside nothing else. Nemotron's decode step updates a Mamba-2
    layer's states where they lie in the run's stack
    (`_updates_its_states_where_they_lie`). A decode step writes its
    new rows through `write_blocks`
    (`_writes_its_rows_through_the_kernel`). GLM-5.2 at
    16 slots x 16,384; Nemotron 3 Super at 64 x 4,096,
    whose Mamba-2 state rides the same carry as leaves with no sequence
    axis; Command A+ at 16 x 16,384, whose sliding layers' rings of
    4,096 rows ride it beside the full layer's rows. `products`: the grouped products an expert layer has, three of
    a gated SwiGLU, two of relu^2."""
    from ray_tpu.ops import (attention, block_rows, grouped_matmul,
                             ssm_update, stacked_product)

    monkeypatch.setattr(grouped_matmul, "on_tpu", lambda: True)
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    monkeypatch.setattr(block_rows, "on_tpu", lambda: True)
    monkeypatch.setattr(ssm_update, "on_tpu", lambda: True)
    monkeypatch.setattr(stacked_product, "on_tpu", lambda: True)
    cfg, params, cache, compiled = _engines_program(
        one_chip, name, program, bucket,
        ("pair_overflows", "pairs_held", "pairs_routed"))
    text = compiled.as_text()
    if name == "nemotron-3-super-serve":
        _updates_its_states_where_they_lie(
            _scheduled(text), program,
            [run["ssm"].shape for run in cache["runs"] if "ssm" in run])
        # A decode step reads a Mamba-2 layer's output projection where
        # it lies, one kernel a Mamba-2 run; a prefill names nothing.
        ssm = [run["wo"].shape[1:] for run in params["runs"]
               if "w_xbc" in run]
        _reads_its_weights_where_they_lie(
            _scheduled(text), text, len(ssm) if program == "decode" else 0,
            *(ssm if program == "decode" else ()))
    elif (name, program) == ("command-a-plus-serve", "decode"):
        # `wq` where it lies, [D, 128 heads, 128] as named, one kernel a
        # run: the scan's slice of 134 MB a `sliding` layer is gone.
        _reads_its_weights_where_they_lie(
            _scheduled(text), text, len(params["runs"]),
            (cfg.dim, cfg.n_heads, cfg.head_dim))
    else:  # (no half of GLM-5.2's names a leaf, nor any prefill's)
        _reads_its_weights_where_they_lie(_scheduled(text), text, 0)
    # A decode step's rows go in by one kernel a run of layers that
    # keep keys (GLM-5.2's three leaves of unlike widths in one call,
    # Command A+'s rings as its full rows, Nemotron's one attention
    # layer; a Mamba-2 run keeps none), a prefill's by one window.
    keyed = [[leaf.shape for key, leaf in run.items()
              if key not in ("ssm", "conv")] for run in cache["runs"]]
    _writes_its_rows_through_the_kernel(
        text, sum(map(bool, keyed)) if program == "decode" else 0,
        *(leaf for run in keyed for leaf in run))
    kernels = re.findall(r"%([\w-]+?)(?:\.\d+)? = \S+ custom-call\(", text)
    # Each run of expert layers is one scan, whose body holds the
    # layer's products once: the held path's kernel and no other.
    runs = sum("we1" in run for run in params["runs"])
    assert [k for k in kernels if k in PRODUCTS] == ["gmm"] * (products * runs)
    assert "ragged-dot" not in text
    # A prefill of Command A+ holds the flash kernel for a call from
    # position 0, once a run of like layers (windowed and full), beside
    # the attention by blocks for any other call.
    assert kernels.count("flash_fwd") == (
        2 if (name, program) == ("command-a-plus-serve", "prefill") else 0)
    # The grouped products read a layer's experts in the run's stack:
    # no op makes an array of one layer's expert matrices (the layer
    # scan's slice of them was a copy of 403 and 704 MB a matrix and
    # layer, a third of a decode step: PERF.md, PR 35).
    e, w, f = cfg.n_experts_held, cfg.latent_dim or cfg.dim, cfg.hidden_dim
    assert not re.findall(
        rf"= bf16\[{e},(?:{w},{f}|{f},{w})\]\S* "
        r"(?:fusion|copy|copy-start|dynamic-slice)\(", text)
    if (name, program) == ("command-a-plus-serve", "decode"):
        # The attention projections enter their products as they lie:
        # the step schedules no `copy` that lays a layer's `wq`, `wk` or
        # `wv` out anew (the rotary turn had the compiler do that, to
        # find q in pairs: 134 MB read and written a `sliding` layer,
        # 1.4 ms of a 16.1 ms step: PERF.md, PR 40). The layer scan's
        # slices of `wk` and `wv` out of the run's stack stay (8.4 MB
        # each; `wq`'s went with PR 60, above).
        d, k = cfg.dim, cfg.head_dim
        assert not re.findall(
            rf"= bf16\[1,{d},(?:{cfg.n_heads}|{cfg.n_kv_heads}),{k}\]\S* "
            r"copy\(", _scheduled(text))
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes > resident  # weights and cache
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 16.0e9


def _updates_its_states_where_they_lie(scheduled, program, stacks):
    """A decode step of a stack with Mamba-2 layers: one `ssm_update`
    kernel a Mamba-2 run (`stacks`: the runs' state leaves), in the
    body under `ssm/ssm_update`, and the state leaf aliased through it.
    A second reader of the carried stack there would show as a `copy`
    of it, the plain recurrence as a fusion that makes a layer's states
    and an `add_dynamic-update-slice_fusion` that writes them into the
    stack (268 MB a layer moved three times, three tenths of the step:
    PERF.md, PR 58). A prefill is the chunked scan and holds none of
    the kernel."""
    calls = re.findall(r'%ssm_update(?:\.\d+)? = .* custom-call\('
                       r'.*op_name="([^"]*)"', scheduled)
    if program != "decode":
        assert not calls
        return
    assert len(calls) == len(stacks) == 2
    assert all(re.search(r"while/body/(?:closed_call/)?ssm/ssm_update/",
                         path) for path in calls)
    made = re.findall(r"%[\w.-]+ = (.*?) (?:copy|copy-start|fusion|convert|"
                      r"dynamic-slice|dynamic-update-slice)\(", scheduled)
    assert made
    for stack in stacks:
        assert stack[1:] == (64, 128, 64, 128)
        for dims in {stack, (1,) + stack[1:], stack[1:]}:
            array = f"f32[{','.join(map(str, dims))}]"
            assert not [shapes for shapes in made if array in shapes], array


def _writes_its_rows_through_the_kernel(text, calls, *leaves):
    """A decode program's new rows into the slot cache: `calls`
    `write_blocks` kernels in all, one a scan of attention layers in
    the body under `attn` for all of the layer's leaves, where the
    carried stacks lie; no scatter a slot, leaf and layer under `attn`
    (PERF.md, PR 54: 1,024 windows were an eighth of the dense step),
    and no op of the mixer's that leaves an array of a stack's or a
    layer's shape (`leaves`: the stacks' shapes; their views reach the
    kernel as bitcasts. Of the mixer's, by its scope: GLM-5.2's rotary
    keys of a run of one layer, 33 MB, the compiler itself moves to and
    from faster memory around the step, with the scatter as with the
    kernel). A prefill (`calls` 0) writes one window a leaf, no
    kernel."""
    paths = re.findall(r'%write_blocks(?:\.\d+)? = .* custom-call\('
                       r'.*op_name="([^"]*)"', text)
    assert len(paths) == calls
    assert all(re.search(r"while/body/(?:closed_call/)?attn/", path)
               for path in paths)
    if not calls:
        return
    assert not re.findall(r' scatter\(.*op_name="[^"]*/attn/', text)
    scheduled = _scheduled(text)
    for leaf in leaves:
        for dims in (leaf, (1,) + tuple(leaf[1:]), tuple(leaf[1:])):
            dims = ",".join(map(str, dims))
            assert not re.findall(
                rf"= \w+\[{dims}\]\S* (?:fusion|copy|copy-start|"
                r"dynamic-slice|convert|transpose)\(.*"
                r'op_name="[^"]*/attn/', scheduled), dims


def _reads_the_cache_through_the_kernel(scheduled, scans, *regions):
    """A decode program's attention over the slot cache: one
    `decode_attention` call a scan of attention layers, in the body
    under `attn`, handed the carried stacks as they are, so that no op
    leaves an array of a layer's keys or values (`regions`: the shapes
    such an array could have; PERF.md, PR 43: the dense step's slice of
    a layer out of the stack was a fifth of its time, and both steps
    read a slot's whole region whatever it held)."""
    calls = re.findall(r'%decode_attention(?:\.\d+)? = .* custom-call\('
                       r'.*op_name="([^"]*)"', scheduled)
    assert len(calls) == scans
    assert all(re.search(r"while/body/(?:closed_call/)?attn/.*"
                         r"decode_attention", path) for path in calls)
    for region in regions:
        dims = ",".join(map(str, region))
        assert not re.findall(
            rf"= \w+\[(?:1,)?{dims}\]\S* (?:fusion|copy|copy-start|"
            r"dynamic-slice|convert|transpose)\(", scheduled)


def test_the_dense_decode_step_compiles_for_the_v5e(one_chip, monkeypatch):
    """Mistral-7B's served cut (`mistral-7b-v0.3-serve.json`, 16 layers,
    32 slots of 1,024) through the engine's own decode program: the
    layer scan's body holds one `decode_attention` call, which takes the
    carried K and V stacks [16, 32, 1024, 8, 128] as [16, 32, 8192, 128]
    (a bitcast: the TPU tiles 8 rows of 128 lanes either way), and the
    layer's keys and values are never sliced out of them; in front of
    it one `write_blocks` call puts the layer's 32 new rows, K's and
    V's, into the same view where the stacks lie."""
    from ray_tpu.ops import attention, block_rows, stacked_product

    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    monkeypatch.setattr(block_rows, "on_tpu", lambda: True)
    monkeypatch.setattr(stacked_product, "on_tpu", lambda: True)
    cfg, _, cache, compiled = _engines_program(
        one_chip, "mistral-7b-v0.3-serve", "decode", 0, ())
    _, n, rows = cache["k"].shape[:3]
    text = compiled.as_text()
    heads, d = cfg.n_kv_heads, cfg.head_dim
    # The three projections are read where they lie in the stack of
    # layers, [D, heads, 128] as named, a head's matrix gathered by
    # strided loads: the scan's slices of `wq` (33.6 MB) and of `wk`
    # and `wv` (8.4 MB each) a layer are gone (PERF.md, PR 60).
    _reads_its_weights_where_they_lie(
        _scheduled(text), text, 3, (cfg.dim, cfg.n_heads, d),
        (cfg.dim, heads, d))
    assert cache["k"].shape == (cfg.n_layers, n, rows, heads, d)
    _reads_the_cache_through_the_kernel(
        _scheduled(text), 1, (n, rows, heads, d), (n, rows * heads, d))
    # The stacks reach the kernels as bitcasts of the carried leaves:
    # the layer's new rows are written into that view, K and V by one
    # `write_blocks` call, and attention reads what comes back.
    assert len(re.findall(
        rf"= bf16\[{cfg.n_layers},{n},{rows * heads},{d}\]\S* bitcast\(",
        text)) == 2
    _writes_its_rows_through_the_kernel(text, 1, cache["k"].shape)
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 0.1e9
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 16.0e9


@pytest.mark.parametrize("program,bucket", [("decode", 0),
                                            ("prefill", 2048)])
def test_olmo_hybrids_step_compiles_for_the_v5e(one_chip, program, bucket,
                                                monkeypatch):
    """Olmo-Hybrid-7B's first stage as `olmo-hybrid-7b-serve.json` cuts
    it, through the engine's own programs at the cell's 32 slots of
    2,048: it compiles and fits, and no step lays a leaf of keys out
    anew. With the leaf as [..., max_seq, 30, 128] the TPU keeps it
    with heads before rows and a decode step copied every leaf there
    and back (5.9 GB of temporaries, over the chip); with heads merged
    and attention on a [max_seq, 30, 128] view it copied a layer's keys
    and values once a step (`models/olmo_hybrid.py` says what a decode
    step does instead: `ops.attention.decode_attention` is handed the
    stacks whole). A decode step updates a delta layer's states
    where they lie in the run's stack and a full layer's new rows
    where they lie in its (`ops/delta_update.py`'s kernel and
    `ops/block_rows.py`'s, which this process's CPU backend would not
    choose, as it would not the attention's: the test says it is on a
    TPU): nothing else makes
    an array of a layer's states or of the stack."""
    from ray_tpu.ops import (attention, block_rows, delta_update,
                             stacked_product)

    monkeypatch.setattr(delta_update, "on_tpu", lambda: True)
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    monkeypatch.setattr(block_rows, "on_tpu", lambda: True)
    counts = ("delta_scan_tokens", "delta_state_resets")
    if program == "prefill":
        # A prefill's halves name nothing: its program is, to the
        # letter, the one lowered where no decode step would either.
        without = _engines_program(one_chip, "olmo-hybrid-7b-serve",
                                   program, bucket, counts, compile=False)[3]
    monkeypatch.setattr(stacked_product, "on_tpu", lambda: True)
    cfg, params, cache, compiled = _engines_program(
        one_chip, "olmo-hybrid-7b-serve", program, bucket, counts)
    _, n, rows = cache["runs"][1]["k"].shape[:3]
    text = compiled.as_text()
    scheduled = _scheduled(text)
    if program == "prefill":
        _reads_its_weights_where_they_lie(scheduled, text, 0)
        assert without.as_text() == _engines_program(
            one_chip, "olmo-hybrid-7b-serve", program, bucket, counts,
            compile=False)[3].as_text()
    else:
        # A decode step reads a delta layer's four big projections and
        # a full layer's three where they lie in the run's stack, as
        # the TPU stores them ([heads, k, D] and [heads, D, k]): the
        # scan's slices of the first (`constant_dynamic-slice_fusion`,
        # 1.4 ms of a 15.1 ms step) and the `copy` that laid the
        # second out anew (0.5 ms) are gone (PERF.md, PR 60).
        linear = [run for run in params["runs"] if "conv_q" in run]
        attended = [run for run in params["runs"] if "conv_q" not in run]
        _reads_its_weights_where_they_lie(
            scheduled, text, 4 * len(linear) + 3 * len(attended),
            *(run[name].shape[1:] for run in linear
              for name in ("wq", "wk", "wv", "wg")),
            *(run[name].shape[1:] for run in attended
              for name in ("wq", "wk", "wv")))
    full = [run["k"].shape for run in cache["runs"] if "k" in run]
    _writes_its_rows_through_the_kernel(
        text, len(full) if program == "decode" else 0, *full)
    width = cfg.n_kv_heads * cfg.head_dim
    assert not re.findall(
        rf"= \w+\[1,{n},{rows},{width}\]\S* (?:copy|convert|transpose)\(",
        scheduled)
    if program == "decode":
        _reads_the_cache_through_the_kernel(
            scheduled, sum("conv_q" not in run for run in params["runs"]),
            (n, rows, width))
        # One kernel a linear run, in its scan's body, and the state
        # leaf aliased through it: a second reader of the carried stack
        # there would show as a `copy` of it (212 MB a layer), the
        # plain recurrence as three fusions over a layer's states
        # (PERF.md, PR 42).
        state = cache["runs"][0]["state"].shape
        assert state == (3, n, cfg.delta_heads, cfg.delta_key_dim,
                         cfg.delta_value_dim)
        calls = re.findall(r'%delta_update(?:\.\d+)? = .* custom-call\('
                           r'.*op_name="([^"]*)"', scheduled)
        assert len(calls) == sum("conv_q" in run for run in params["runs"])
        assert all(re.search(r"while/body/(?:closed_call/)?delta/"
                             r"delta_update/", path) for path in calls)
        stack, layer = (f"f32[{','.join(map(str, dims))}]"
                        for dims in (state, state[1:]))
        made = re.findall(r"%[\w.-]+ = (.*?) (?:copy|fusion|"
                          r"dynamic-update-slice)\(", scheduled)
        assert made and not [shapes for shapes in made
                             if stack in shapes or layer in shapes]
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes > 10.2e9  # weights and cache
    assert memory.temp_size_in_bytes < 0.5e9
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 16.0e9


@pytest.mark.parametrize("program,bucket", [("decode", 0),
                                            ("prefill", 1024),
                                            ("prefill", 2048)])
def test_sdars_step_compiles_for_the_v5e(one_chip, program, bucket,
                                         monkeypatch):
    """SDAR-30B-A3B's stage as `sdar-30b-a3b-serve.json` cuts it,
    through the block engine's own programs at the cell's 32 slots of
    2,048: the step of two blocks a slot compiles and fits, writes
    their rows through `write_blocks` and reads the slot cache through
    `decode_attention` once for both (a block's 4 x 32 queries as query
    heads of their key heads, a length a block, the stacks handed
    whole: no op leaves an array of a layer's keys), runs the head on
    one block a slot, and runs every expert through the held
    path's grouped kernel where the matrices lie (no layer's 1.2 GB of
    experts is sliced out of the scan's stack); a prefill holds the
    flash kernel with the block mask beside the plain path, and
    computes no head."""
    from benchmark.harness.manifest import ROOT, load_json, model_adapter
    from ray_tpu.models.serving import served_model
    from ray_tpu.ops import attention, block_rows, grouped_matmul
    from ray_tpu.serve.llm import _BlockEngine

    monkeypatch.setattr(grouped_matmul, "on_tpu", lambda: True)
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    monkeypatch.setattr(block_rows, "on_tpu", lambda: True)
    config = load_json(ROOT, "benchmark", "configs",
                       "sdar-30b-a3b-serve.json")
    model = model_adapter(config)
    cfg = model.program_config(config)
    n, rows = config["serve"]["max_batch_size"], config["serve"]["max_seq_len"]
    b = cfg.block_length

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    def ints(*dims, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    params = on_chip(jax.eval_shape(
        lambda: model.init(cfg, jax.random.PRNGKey(0))))
    engine = _BlockEngine.__new__(_BlockEngine)  # its programs, no device
    engine.cfg, engine._served = cfg, served_model(cfg)
    engine._step_len = b
    cache = on_chip(jax.eval_shape(
        lambda: engine._served.init_cache(cfg, n, rows)))
    engine.max_seq, engine.decode_steps, engine.n_slots = rows, 1, n
    engine._count_names = ("experts_touched", "pairs_routed")
    if program == "decode":
        compiled = jax.jit(engine._decode_impl, donate_argnums=(1,)).lower(
            params, cache, ints(n, b), ints(n, b, dtype=jnp.bool_),
            ints(n, b), ints(n), ints(n), ints(n, b),
            ints(n, dtype=jnp.bool_), ints(n, dtype=jnp.float32), ints(n),
            ints(2, dtype=jnp.uint32)).compile()
    else:
        compiled = jax.jit(engine._prefill_impl, donate_argnums=(1,),
                           static_argnums=(6,)).lower(
            params, cache, ints(1, bucket), ints(), ints(), ints(),
            bucket).compile()
    text = compiled.as_text()
    kernels = re.findall(r"%([\w-]+?)(?:\.\d+)? = \S+ custom-call\(", text)
    assert [k for k in kernels if k in PRODUCTS] == ["gmm"] * 3
    assert "ragged-dot" not in text
    e, d, f = cfg.n_experts, cfg.dim, cfg.hidden_dim
    assert not re.findall(
        rf"= bf16\[{e},(?:{d},{f}|{f},{d})\]\S* "
        r"(?:fusion|copy|copy-start|dynamic-slice)\(", text)
    scheduled = _scheduled(text)
    width = cfg.n_kv_heads * cfg.head_dim
    if program == "decode":
        _reads_the_cache_through_the_kernel(scheduled, 1, (n, rows, width))
        assert kernels.count("flash_fwd") == 0
        # Both blocks' rows, K's and V's, are written by one kernel a
        # layer where the stacks lie: no scatter a block and leaf.
        assert len(re.findall(r"%write_blocks(?:\.\d+)? = .* custom-call\(",
                              text)) == 1
        assert not re.findall(r' scatter\(.*op_name="[^"]*/attn/', text)
        # The head's product and the float32 pass over the logits are
        # of the block being denoised alone.
        assert f"f32[{n},{b},{cfg.vocab_size}]" in text
        assert f"[{n},{2 * b},{cfg.vocab_size}]" not in text
    else:
        assert kernels.count("flash_fwd") == 1
        # No logits are read, so the head is not computed.
        assert f"{cfg.vocab_size}]" not in text
    memory = compiled.memory_analysis()
    # Weights and cache; a prefill is handed neither head nor embedding
    # it does not read.
    assert memory.argument_size_in_bytes > (
        9.5e9 if program == "decode" else 8.8e9)
    assert memory.temp_size_in_bytes < 1.0e9
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 16.0e9


@pytest.mark.parametrize("program,bucket", [("decode", 0),
                                            ("prefill", 512),
                                            ("prefill", 1024),
                                            ("prefill", 2048)])
def test_lfm2s_step_compiles_for_the_v5e(one_chip, program, bucket,
                                         monkeypatch):
    """LFM2-8B-A1B's first stage as `lfm2-8b-a1b-serve.json` cuts it,
    through the engine's own programs at the cell's 64 slots of 2,048:
    it compiles and fits, heads of 64 channels and all. A decode step
    reads the three full layers' keys through `decode_attention` on the
    merged leaf of 8 x 64 = 512 channels (a head's slice of 64 lanes
    starts at a multiple of 64) and writes their rows through
    `write_blocks`; a prefill holds the flash kernel over blocks
    [rows, 64], once a run of full layers; every expert layer's three
    products are the held path's kernel on the run's stack; and no step
    copies a leaf of the cache: a conv layer's two rows a slot are read
    and written where the stack lies (5.8 MB in all), and a prefill
    whose bucket is under the region (every one the cell's prompts
    take but 2,048, which overwrites the region and reads none of it)
    keeps the key leaves in the order they lie in: with a view [rows,
    8, 64] of a layer left to the compiler, every leaf was copied whole
    with its rows in the lanes, 6 x 134 MB a call (`lfm2_moe._attention`
    pins the layer's rows)."""
    from ray_tpu.ops import attention, block_rows, grouped_matmul

    monkeypatch.setattr(grouped_matmul, "on_tpu", lambda: True)
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    monkeypatch.setattr(block_rows, "on_tpu", lambda: True)
    cfg, params, cache, compiled = _engines_program(
        one_chip, "lfm2-8b-a1b-serve", program, bucket,
        ("conv_prefill_tokens", "conv_state_resets", "experts_held_steps",
         "experts_touched", "pair_overflows", "pairs_held", "pairs_routed"))
    n, rows = cache["runs"][1]["k"].shape[1:3]
    assert (n, rows, cfg.head_dim) == (64, 2048, 64)
    text = compiled.as_text()
    scheduled = _scheduled(text)
    full = [run["k"].shape for run in cache["runs"] if "k" in run]
    assert full == [(1, n, rows, 512)] * 3
    _writes_its_rows_through_the_kernel(
        text, len(full) if program == "decode" else 0, *full)
    assert not re.findall(
        rf"= \w+\[1,{n},{rows},512\]\S* (?:copy|convert|transpose)\(",
        scheduled)
    kernels = re.findall(r"%([\w-]+?)(?:\.\d+)? = \S+ custom-call\(", text)
    sparse = sum("we1" in run for run in params["runs"])
    assert sparse == 6
    assert [k for k in kernels if k in PRODUCTS] == ["gmm"] * (3 * sparse)
    assert "ragged-dot" not in text
    if program == "decode":
        _reads_the_cache_through_the_kernel(scheduled, len(full),
                                            (n, rows, 512))
        assert "flash_fwd" not in kernels
    else:
        assert kernels.count("flash_fwd") == len(full)
        assert "decode_attention" not in kernels
    # No op makes an array of a run's carried rows or of one layer's
    # expert matrices.
    for run in cache["runs"]:
        if "conv" in run:
            dims = ",".join(map(str, run["conv"].shape))
            assert not re.findall(
                rf"= bf16\[{dims}\]\S* (?:copy|copy-start|transpose)\(",
                scheduled), dims
    e, d, f = cfg.n_experts, cfg.dim, cfg.hidden_dim
    assert not re.findall(
        rf"= bf16\[{e},(?:{d},{f}|{f},{d})\]\S* "
        r"(?:fusion|copy|copy-start|dynamic-slice)\(", text)
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes > 10.1e9  # weights and cache
    assert memory.temp_size_in_bytes < 0.7e9
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 16.0e9


@pytest.mark.parametrize("program,bucket", [("decode", 0),
                                            ("prefill", 1536)])
def test_kimi_linears_step_compiles_for_the_v5e(one_chip, program, bucket,
                                                monkeypatch):
    """Kimi-Linear-48B-A3B's share as `kimi-linear-48b-a3b-serve.json`
    cuts it, through the engine's own programs at the cell's 64 slots
    of 4,096: it compiles and fits. A decode step updates a KDA layer's
    states, 32 heads of [128, 128] float32 a slot, where they lie in
    the run's stack, through `ops/delta_update.py`'s one kernel with
    the decay a column a head, the stack aliased through it: one call
    a run of KDA layers, in its scan's body, and nothing else makes an
    array of the stack or of a layer of it (537 MB). A latent layer's
    new rows go into the `latent` and `rope` leaves through
    `write_blocks`, and neither leaf is copied, converted or laid out
    anew by either program (with the shared key channels in rows of 64
    the TPU kept that leaf with its positions in the lanes and every
    step copied it there and back: `kimi_linear._shared_row`); every expert layer's three products are
    the held path's kernel on the run's stack."""
    from ray_tpu.ops import attention, block_rows, delta_update
    from ray_tpu.ops import grouped_matmul, stacked_product

    monkeypatch.setattr(grouped_matmul, "on_tpu", lambda: True)
    monkeypatch.setattr(delta_update, "on_tpu", lambda: True)
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    monkeypatch.setattr(block_rows, "on_tpu", lambda: True)
    monkeypatch.setattr(stacked_product, "on_tpu", lambda: True)
    cfg, params, cache, compiled = _engines_program(
        one_chip, "kimi-linear-48b-a3b-serve", program, bucket,
        ("delta_scan_tokens", "delta_state_resets", "experts_held_steps",
         "experts_touched", "latent_keys_read", "pair_overflows",
         "pairs_held", "pairs_routed"))
    text = compiled.as_text()
    scheduled = _scheduled(text)
    latent = [run["latent"].shape for run in cache["runs"]
              if "latent" in run]
    n, rows = latent[0][1:3]
    assert (n, rows) == (64, 4096)
    assert latent == [(1, n, rows, cfg.kv_lora_rank)] * 3
    shared = (1, n, rows, 128)  # the 64 shared channels in rows of lanes
    assert [run["rope"].shape for run in cache["runs"] if "rope" in run] \
        == [shared] * 3
    _writes_its_rows_through_the_kernel(
        text, len(latent) if program == "decode" else 0, latent[0], shared)
    for width in (cfg.kv_lora_rank, 128):
        assert not re.findall(
            rf"= \w+\[1,{n},{rows},{width}\]\S* (?:copy|convert|transpose)\(",
            scheduled), width
    kernels = re.findall(r"%([\w-]+?)(?:\.\d+)? = \S+ custom-call\(", text)
    sparse = sum("we1" in run for run in params["runs"])
    assert sparse == 6
    assert [k for k in kernels if k in PRODUCTS] == ["gmm"] * (3 * sparse)
    assert "ragged-dot" not in text
    states = [run["state"].shape for run in cache["runs"] if "state" in run]
    assert states == [(layers, n, 32, 128, 128) for layers in (1, 2, 3, 3)]
    # A decode step reads a KDA layer's `wq`, `wk` and `wv` where they
    # lie in the run's stack, [D, 32 heads, 128] as named, a head's
    # matrix gathered by strided loads: the scan's three slices of
    # 18.9 MB a layer are gone (PERF.md, PR 60).
    _reads_its_weights_where_they_lie(
        scheduled, text, 3 * len(states) if program == "decode" else 0,
        *([(cfg.dim, 32, 128)] if program == "decode" else ()))
    made = re.findall(r"%[\w.-]+ = (.*?) (?:copy|copy-start|fusion|"
                      r"dynamic-update-slice)\(", scheduled)
    for state in states if program == "decode" else ():
        # One kernel a KDA run, in its scan's body, the state leaf
        # aliased through it: a second reader of the carried stack
        # there would show as a `copy` of it, the plain recurrence as
        # fusions over a layer's states.
        stack, layer = (f"f32[{','.join(map(str, dims))}]"
                        for dims in (state, state[1:]))
        assert made and not [shapes for shapes in made
                             if stack in shapes or layer in shapes], state
    if program == "decode":
        calls = re.findall(r'%delta_update(?:\.\d+)? = .* custom-call\('
                           r'.*op_name="([^"]*)"', scheduled)
        assert len(calls) == len(states)
        assert all(re.search(r"(?:while/body/(?:closed_call/)?)?delta/"
                             r"delta_update/", path) for path in calls)
    else:
        assert "delta_update" not in kernels
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes > 8.4e9  # weights and cache
    assert memory.temp_size_in_bytes < 1.0e9
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 16.0e9


@pytest.mark.parametrize("window", [None, 4096], ids=["full", "window"])
def test_trained_flash_kernels_compile_at_smallthinkers_shapes(one_chip,
                                                               window):
    """A sequence of 16,384 tokens, 28 query heads on 4 key heads of
    128, forward and backward at blocks of 1024: with a window the
    dk/dv kernel's tile asks more fast memory than the compiler's
    default allows, which `ops.attention._WINDOWED_PARAMS` grants."""
    from ray_tpu.ops import attention

    def shape(heads):
        return jax.ShapeDtypeStruct((1, heads, 16384, 128), jnp.bfloat16,
                                    sharding=one_chip)

    def loss(q, k, v):
        return attention._flash(q, k, v, True, 128 ** -0.5, 1024, 1024,
                                False, window).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        shape(28), shape(4), shape(4)).compile().as_text()
    kernels = re.findall(r"%\w*?(flash_(?:fwd|bwd_dq|bwd_dkv))_*\.\d+ = "
                         r".* custom-call\(", text)
    assert sorted(kernels) == ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]


def test_a_trained_share_compiles_for_the_v5e(one_chip):
    """SmallThinker's expert layer at the cell's shapes: 65,536 tokens,
    6 of 64 experts a token, 16 ReGLU experts of width 768 held. The
    first buffer's 3 grouped products forward and 6 backward and, in the
    loops of the buffers beyond it, the same again (the backward loop
    recomputes a buffer, so 3 + 9); no scatter of rows."""
    import dataclasses

    cfg = dataclasses.replace(
        moe.MoEConfig.debug_moe(), dim=2560, hidden_dim=768, n_experts=64,
        n_experts_per_token=6, experts_held=(0, 16), expert_kind="reglu",
        dtype=jnp.bfloat16)

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def loss(x, gates, top_i, we1, we3, we2):
        out, held, over, touched = moe._held_experts_trained(
            cfg, x, gates, top_i, we1, we3, we2)
        return out.astype(jnp.float32).sum()

    compiled = jax.jit(
        jax.value_and_grad(loss, argnums=(0, 1, 3, 4, 5))).lower(
        shape(65536, 2560), shape(65536, 6, dtype=jnp.float32),
        shape(65536, 6, dtype=jnp.int32), shape(16, 2560, 768),
        shape(16, 2560, 768), shape(16, 768, 2560)).compile()
    text = compiled.as_text()
    kernels = re.findall(r"%([\w-]+?)(?:\.\d+)? = \S+ custom-call\(", text)
    assert sum(k in PRODUCTS for k in kernels) == 9 + 3 + 9
    assert not re.search(r"\[\d+,\d+\]\S* scatter\(", text)
    # A buffer is 1.5 times the even share's 98,304 rows, and no array
    # of all 393,216 pairs' rows stands in memory.
    assert "[147456,2560]" in text and "[393216,2560]" not in text
