"""The shape of the compiled decode step (ISSUE 30, ISSUE 32), and that only it
changed.

The step is compiled for a v5e that is described and not attached, from
shapes, at the engine keys of the serving cells: the page buffers ride
the layer scan's carry and are updated in place, so the program holds them
once. A scan that took them as `xs` and gave them back as `ys` held a second
copy of both (2.5 and 3.84 GiB of temporaries) and copied them whole every
step. Since ISSUE 32 nothing shaped like the tables' whole gather exists
either (0.26 GiB of temporaries at the open loop's shapes): the layer loop's
body holds the one loop that walks the list of live pages, and what it gathers
is a chunk of that list.
The topology is described inside a fixture, never at import. Two other
files load the TPU's compiler (`tests/perfbench/test_perfbench_aot.py`,
`tests/run_pallas/test_softmax_bwd_aot.py`): without the driver's
ALLOW_MULTIPLE_LIBTPU_LOAD the files that land on later workers skip.

The rest pins what shares `llama.scan_passes` with the step and must not have
moved: prefill of one bucket against `models/generate`, and the gradient of
`llama.loss_fn` against the float32 references' own, plain and looped.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from apex_tpu.models import generate as gen
from apex_tpu.models import llama
from apex_tpu.ops import pallas_config
from apex_tpu.serving import scheduler as sched
from perfbench import harness
from perfbench.references import llama_dense, ouro_looped
from perfbench.references.common import seed_words
from perfbench.runners import serve, serve_looped


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no libtpu here, or another process has it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def read(kind, name):
    return harness.load_json(harness.ROOT, "perfbench", kind, name + ".json")


def gmm_calls(text):
    """The compiled program's calls of the Pallas grouped product: Mosaic
    custom calls named after `pallas_call(name="apex_gmm")`."""
    return re.findall(r"%apex_gmm[\w.\-]* = .* custom-call\(", text)


def computations(text):
    """The compiled module's computations by name, each its lines."""
    found, lines = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?(%?[\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            lines = found[head.group(1)] = []
        elif lines is not None:
            lines.append(line)
    return found


@pytest.mark.parametrize("config,mix,runner", [
    ("mistral7b_v03_d16", "longgen_backlog", serve),
    ("ouro_2p6b", "reasoning_backlog", serve_looped),
    ("mistral7b_v03_d16", "longprompt_poisson", serve)],
    ids=["plain", "looped", "open_loop"])
def test_the_compiled_step_holds_the_page_buffers_once(one_chip, config, mix,
                                                       runner):
    cfg = runner.model_config(read("configs", config))
    eng = read("traffic", mix)["engine"]
    page, rows = eng["page_size"], eng["max_batch"]
    table = sched.pages_per_request(eng["max_prompt_len"], eng["max_new_cap"],
                                    page)
    shape = (cfg.cache_layers, eng["num_pages"] + 1, page, cfg.num_kv_heads,
             cfg.head_dim)

    def struct(s, dtype):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda a: struct(a.shape, a.dtype),
        jax.eval_shape(lambda: llama.init_params(jax.random.PRNGKey(0), cfg)))
    pages = struct(shape, cfg.dtype)
    step = jax.jit(sched.build_decode_step(cfg, page), donate_argnums=(2, 3))
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = step.lower(
            params, {}, pages, pages, None, struct((rows,), jnp.int32),
            struct((rows, table), jnp.int32), struct((rows,), jnp.int32),
            struct((rows,), jnp.bool_), struct((rows,), jnp.bool_),
            struct((rows,), jnp.int32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    both = 2 * int(np.prod(shape)) * jnp.dtype(cfg.dtype).itemsize
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == both          # arguments 2 and 3
    assert memory.temp_size_in_bytes < 0.05 * 2 ** 30
    text = compiled.as_text()
    assert "jit__decode_step" in text
    # as the cache has them, as the scan carries them (layers x pages), and
    # as the tables' whole gather had them (rows x positions)
    shapes = {",".join(map(str, s)) for s in (
        shape, (shape[0] * shape[1],) + shape[2:],
        (rows, table * page) + shape[3:])}
    copies = re.findall(r"= \w+\[([\d,]+)\]\S* copy\(", text)
    assert copies and not shapes & set(copies)
    # the layer loop, and inside its body the list's loop and no other
    bodies = [computations(text)[name] for name in re.findall(
        r" while\(.*body=(%?[\w.\-]+)", text)]
    nested = [sum(" while(" in line for line in body) for body in bodies]
    assert sorted(nested) == [0, 1]


def test_the_expert_models_step_reads_its_experts_where_they_lie(one_chip):
    """ISSUE 33's cell: the step of a model with a dense lead and four expert
    layers holds the page buffers once too, and no layer's experts are cut
    out of their stack: a scan that took the `[4, 32, 3072, 3072]` weights as
    its `xs` compiled to a 604 MB dynamic-slice a weight and layer (0.57 GiB
    of temporaries, 14 GB moved a step). The grouped products are the Pallas
    kernel `apex_gmm` (ISSUE 36), one a weight, which finds the layer's 32
    groups in the whole stack; XLA's ragged dot is in the step no more."""
    from perfbench.runners import serve_afmoe

    cfg = serve_afmoe.model_config(read("configs", "trinity_large_ep8_d5"))
    eng = read("traffic", "longctx_backlog")["engine"]
    page, rows = eng["page_size"], eng["max_batch"]
    table = sched.pages_per_request(eng["max_prompt_len"], eng["max_new_cap"],
                                    page)
    assert (rows, table, rows * table) == (32, 56, eng["num_pages"])
    shape = (cfg.cache_layers, eng["num_pages"] + 1, page, cfg.num_kv_heads,
             cfg.head_dim)

    def struct(s, dtype):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    shapes = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(
        shapes)) == 4_321_902_848 + 4 * 256
    params = jax.tree_util.tree_map(lambda a: struct(a.shape, a.dtype),
                                    shapes)
    pages = struct(shape, cfg.dtype)
    step = jax.jit(sched.build_decode_step(cfg, page), donate_argnums=(2, 3))
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        with pallas_config.force("on"):      # as on the chip: 'auto' sees a CPU
            compiled = step.lower(
                params, {}, pages, pages, None,
                struct((rows + len(sched.EXPERT_COUNTS),), jnp.int32),
                struct((rows, table), jnp.int32), struct((rows,), jnp.int32),
                struct((rows,), jnp.bool_), struct((rows,), jnp.bool_),
                struct((rows,), jnp.int32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == 2 * int(np.prod(shape)) * 2
    # the slot tables of `[32 x 56]` slots are 44 MB of float32 a layer
    assert memory.temp_size_in_bytes < 0.1 * 2 ** 30
    weights = memory.argument_size_in_bytes - memory.alias_size_in_bytes
    assert 8.0 < weights / 2 ** 30 < 8.1
    text = compiled.as_text()
    assert len(gmm_calls(text)) >= 3 and "ragged-dot" not in text
    assert not re.findall(
        r"= bf16\[(?:1,)?32,3072,3072\]\S* (?:dynamic-slice|copy|fusion)\(",
        text)


def test_the_hybrid_models_step_holds_its_state_buffer_once(one_chip):
    """ISSUE 35's cell: nine layers at LFM2-24B-A2B's widths, seven of them
    gated short convolutions. The conv layers' state buffer `[7, 2, 32,
    2048]` is donated and rides the scan's carry beside the page buffers:
    the outputs alias all three arguments and no second copy of it is made;
    the pool has pages of the two attention layers alone (0.5 MiB a page:
    ISSUE 35's "1 MiB" doubles it); all
    64 experts of the eight expert layers are read where they lie, `[512,
    ...]` a weight, and no layer's are cut out; the runs' scans are five
    loops, the list's loop inside the attention runs' alone."""
    from perfbench.runners import serve_lfm2

    cfg = serve_lfm2.model_config(read("configs", "lfm2_24b_a2b_d9"))
    eng = read("traffic", "docextract_backlog")["engine"]
    page, rows = eng["page_size"], eng["max_batch"]
    table = sched.pages_per_request(eng["max_prompt_len"], eng["max_new_cap"],
                                    page)
    assert (rows, table, rows * table) == (32, 33, eng["num_pages"])
    assert (cfg.cache_layers, cfg.conv_layers) == (2, 7)
    from apex_tpu.serving.kv_cache import (page_dims, page_hbm_bytes,
                                           state_hbm_bytes)
    # heads of 64 lie side by side in a page: `[..., 8, 64]` would be padded
    # to twice its bytes and converted whole, in and out, every step
    assert page_dims(cfg) == (512,)
    shape = (2, eng["num_pages"] + 1, page, 512)
    state_shape = (7, 2, rows, cfg.hidden_size)
    assert page_hbm_bytes(cfg, page) == 2 ** 19
    assert state_hbm_bytes(cfg, rows) == int(np.prod(state_shape)) * 2

    def struct(s, dtype):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    shapes = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(
        shapes)) == 5_177_950_464 + 8 * 64
    params = jax.tree_util.tree_map(lambda a: struct(a.shape, a.dtype),
                                    shapes)
    pages, state = struct(shape, cfg.dtype), struct(state_shape, cfg.dtype)
    step = jax.jit(sched.build_decode_step(cfg, page),
                   donate_argnums=(2, 3, 4))
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        with pallas_config.force("on"):      # as on the chip: 'auto' sees a CPU
            compiled = step.lower(
                params, {}, pages, pages, state,
                struct((rows + len(sched.EXPERT_COUNTS),), jnp.int32),
                struct((rows, table), jnp.int32), struct((rows,), jnp.int32),
                struct((rows,), jnp.bool_), struct((rows,), jnp.bool_),
                struct((rows,), jnp.int32)).compile()
            prefill = sched.build_prefill(cfg, 4096).lower(
                params, {}, struct((1, 4096), jnp.int32),
                struct((), jnp.int32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    memory = compiled.memory_analysis()
    state_bytes = int(np.prod(state_shape)) * 2
    assert memory.alias_size_in_bytes == (
        2 * int(np.prod(shape)) * 2 + state_bytes)
    assert memory.temp_size_in_bytes < 0.1 * 2 ** 30
    weights = memory.argument_size_in_bytes - memory.alias_size_in_bytes
    assert 9.6 < weights / 2 ** 30 < 9.7
    text = compiled.as_text()
    assert "jit__decode_step" in text
    assert len(gmm_calls(text)) >= 3 and "ragged-dot" not in text
    assert len(gmm_calls(prefill.as_text())) >= 3
    # neither the state buffer, the pool nor a layer's experts is ever
    # copied whole
    assert not re.findall(
        r"= bf16\[(?:7,2,32,2048|2,1057,128,512|2114,128,512)\]\S* copy\(",
        text)
    assert not re.findall(
        r"= bf16\[(?:1,)?64,(?:2048,1536|1536,2048)\]\S* "
        r"(?:dynamic-slice|copy|fusion)\(", text)
    bodies = [computations(text)[name] for name in re.findall(
        r" while\(.*body=(%?[\w.\-]+)", text)]
    nested = [sum(" while(" in line for line in body) for body in bodies]
    print("while loops and those nested in each:", sorted(nested))
    # a prefill of the longest bucket fits beside the weights and the cache
    pre = prefill.memory_analysis()
    print("decode temp", memory.temp_size_in_bytes / 2 ** 20, "MiB; prefill",
          "temp", pre.temp_size_in_bytes / 2 ** 20, "MiB, out",
          pre.output_size_in_bytes / 2 ** 20, "MiB")
    held = weights + memory.alias_size_in_bytes
    assert (held + pre.temp_size_in_bytes + pre.output_size_in_bytes
            ) / 2 ** 30 < 14.0


TINY = {"hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
        "num_hidden_layers": 2, "vocab_size": 256, "rms_norm_eps": 1e-6,
        "rope_theta": 1000000, "tie_word_embeddings": False,
        "torch_dtype": "float32"}
PLAIN = llama.LlamaConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=2,
    num_heads=4, num_kv_heads=4, max_seq_len=256, rope_theta=1e6,
    rms_eps=1e-6, dtype=jnp.float32)
STACKS = {
    "plain": (PLAIN, llama_dense, TINY),
    "looped": (dataclasses.replace(PLAIN, num_passes=4, sandwich_norm=True),
               ouro_looped, {**TINY, "total_ut_steps": 4})}
TIGHT = 2e-4          # float32 round-off through 8 layer passes, values O(1)


@pytest.fixture(scope="module", params=sorted(STACKS))
def stack(request):
    cfg, ref, tiny = STACKS[request.param]
    return cfg, ref, tiny, ref.init(*seed_words(2 ** 31 + 30), tiny)


def test_prefill_of_one_bucket_is_generates(stack):
    """The first token and K and V of every cache layer, from a prompt of 11
    padded to the bucket of 16, are those of `generate`'s own prefill."""
    cfg, ref, tiny, params = stack
    prompt = jax.random.randint(jax.random.PRNGKey(4), (1, 11), 0, 256)
    padded = jnp.zeros((1, 16), jnp.int32).at[:, :11].set(prompt)
    first, ks, vs = sched.build_prefill(cfg, 16)(params, {}, padded,
                                                 np.int32(11))
    assert ks.shape == vs.shape == (cfg.cache_layers, 16, 4, 16)
    logits = ref.row_logits(params, prompt[0], jnp.asarray([10]), tiny)
    assert float(ref.served_gaps(logits, first)[0]) < TIGHT
    assert int(first[0]) == int(gen.generate(params, prompt, cfg, 1)[0, -1])
    positions = jnp.arange(11)[None]

    _, (want_k, want_v) = llama.scan_passes(
        llama.embed(params, prompt, cfg, tp_axis=None), params, cfg,
        lambda h, lp, _: llama.block(h, lp, cfg, positions,
                                     gen._flash_keeping_kv),
        params["layers"])
    np.testing.assert_allclose(ks[:, :11], want_k[:, 0], atol=TIGHT)
    np.testing.assert_allclose(vs[:, :11], want_v[:, 0], atol=TIGHT)
    # the passes of one layer do not share their K and V
    if cfg.num_passes > 1:
        assert float(jnp.max(jnp.abs(ks[0] - ks[cfg.num_layers]))) > 0.01


def test_the_gradient_of_the_loss_is_the_references(stack):
    cfg, ref, tiny, params = stack
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 24), 0, 256)
    batch = (tokens[:, :-1], tokens[:, 1:])

    def ref_loss(p):
        return jnp.mean(jnp.stack([
            -jax.nn.log_softmax(ref.row_logits(
                p, batch[0][r], jnp.arange(23), tiny))[jnp.arange(23),
                                                        batch[1][r]]
            for r in range(2)]))

    want, want_grads = jax.value_and_grad(ref_loss)(params)
    got, got_grads = jax.value_and_grad(llama.loss_fn)(
        params, batch, cfg, tp_axis=None, cp_axis=None)
    assert float(got) == pytest.approx(float(want), abs=1e-4)
    flat = jax.tree_util.tree_leaves_with_path(want_grads)
    assert len(flat) == len(jax.tree_util.tree_leaves(got_grads))
    for (path, w), g in zip(flat, jax.tree_util.tree_leaves(got_grads)):
        scale = max(float(jnp.max(jnp.abs(w))), 1e-3)
        assert float(jnp.max(jnp.abs(g - w))) < TIGHT * scale, path
    assert float(jnp.max(jnp.abs(got_grads["layers"]["wd"]))) > 0
