"""A stack of gated short-convolution layers beside attention layers, a dense
lead and dropless experts all held (ISSUE 35) through `llama.forward`,
`generate`, the scheduler, the paged cache and the conv layers' state buffer
beside it, judged by the plain reference (`perfbench/references/lfm2_moe.py`)
on the same seeded float32 weights: logits for the forward, and for what is
served the widest gap of a served token's logit below the reference's best, as
the benchmark judges its cell. The published 40-entry layer pattern and the
cut's; prompts that end inside a page and one shorter than the convolution;
planted faults; a row's bits in any batch and in a slot another row has left;
dump and resume; the spans; and what is refused.

Tolerances. TIGHT = 2e-4 on logits of size 4: float32 round-off through 40
layers (read: 3e-5); the same reference with its matmul operands rounded to
bfloat16 lies 0.02 and more away, so a bf16 or fp8 computation fails it.
LIMIT = 0.01 is the tiny cells' `logit_gap_limit` (tests/perfbench): sound
servings read 0 (the served token is the float32 reference's first) and must
stay under a tenth of it; every planted fault reads over it.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import observability as obs
from apex_tpu.models import generate as gen
from apex_tpu.models import llama
from apex_tpu.observability import SpanTracer, set_tracer
from apex_tpu.resilience.faults import FaultPlan
from apex_tpu.resilience.loop import Preempted
from apex_tpu.serving import ServingEngine
from apex_tpu.serving import kv_cache
from apex_tpu.serving import scheduler as sched
from apex_tpu.serving.engine import _PAGES_FILE
from apex_tpu.transformer import moe
from perfbench.references import lfm2_moe as ref
from perfbench.references.common import seed_words
from perfbench.runners import serve_lfm2

PUBLISHED = ["conv", "conv"] + ["full_attention", "conv", "conv",
                                "conv"] * 9 + ["full_attention", "conv"]
CUT = ["conv"] + ["full_attention", "conv", "conv", "conv"] * 2
# heads of 32, four of them for K and V: a position's heads lie side by side
# in a page (kv_cache.page_dims), as the cell's do
TINY = dict(hidden_size=64, num_attention_heads=8, num_key_value_heads=4,
            head_dim=32, intermediate_size=128, moe_intermediate_size=48,
            num_hidden_layers=9, num_dense_layers=1, layer_types=CUT,
            num_experts=8, num_experts_per_tok=4, norm_eps=1e-5,
            rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
            norm_topk_prob=True, routed_scaling_factor=1,
            use_expert_bias=True, conv_L_cache=3, conv_bias=False,
            vocab_size=96, torch_dtype="float32", tie_word_embeddings=True,
            max_position_embeddings=256)
WHOLE = {**TINY, "num_hidden_layers": 40, "num_dense_layers": 2,
         "layer_types": PUBLISHED}
CFG = serve_lfm2.model_config(TINY)
TIGHT, LIMIT, PAGE = 2e-4, 0.01, 4
# (prompt, new): one shorter than the convolution, one as long as its state,
# prompts that end inside a page and one on a page's edge
JOBS = ((1, 6), (2, 5), (3, 6), (8, 10), (19, 10), (18, 9), (5, 12), (6, 12))
SEQ = 32          # every sequence checked is padded to this: one compile


@pytest.fixture(scope="module")
def params():
    return ref.init(*seed_words(2 ** 31 + 35), TINY)


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    monkeypatch.setattr(ref, "QUERY_BLOCK", 8)


def jobs(seed=0, spec=JOBS):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, 96, size=p).astype(np.int32), new)
            for p, new in spec]


def engine_for(params, cfg=CFG, **kw):
    kw.setdefault("registry", obs.MetricRegistry())
    kw.setdefault("max_batch", 3)
    return ServingEngine(params, cfg, page_size=PAGE, num_pages=27,
                         max_prompt_len=20, max_new_cap=12, **kw)


def serve_all(engine, work):
    for prompt, new in work:
        engine.submit(prompt, new)
    return engine.run()


@jax.jit
def reference_gaps(params, padded, rows, served):
    return ref.served_gaps(ref.row_logits(params, padded, rows, TINY), served)


def widest_gap(params, work, results):
    widest = 0.0
    for rid, (prompt, new) in enumerate(work):
        served = np.asarray(results[rid]["tokens"], np.int32)
        assert len(served) == new
        seq = np.concatenate([prompt, served[:-1]])
        rows = np.minimum(len(prompt) - 1 + np.arange(12), len(seq) - 1)
        gaps = reference_gaps(
            params, jnp.asarray(np.pad(seq, (0, SEQ - len(seq)))),
            jnp.asarray(rows), jnp.asarray(np.pad(served, (0, 12 - new))))
        widest = max(widest, float(jnp.max(gaps[:new])))
    return widest


# ------------------------------------------------------------ the model


def test_the_config_counts_layers_by_what_they_keep():
    whole = serve_lfm2.model_config(WHOLE)
    assert (whole.num_layers, whole.conv_layers, whole.cache_layers) == (
        40, 30, 10)
    assert (CFG.num_layers, CFG.conv_layers, CFG.cache_layers) == (9, 7, 2)
    assert CFG.runs == (("conv", False, 1), ("attention", True, 1),
                        ("conv", True, 3), ("attention", True, 1),
                        ("conv", True, 3))
    assert len(whole.runs) == 21 and whole.runs[0] == ("conv", False, 2)
    assert whole.runs[-2:] == (("attention", True, 1), ("conv", True, 1))
    assert (CFG.route_norm_eps, llama.LlamaConfig().route_norm_eps) == (
        1e-6, 1e-20)
    assert not llama.tiny().hybrid and llama.tiny().cache_layers == 2


@pytest.mark.parametrize("tiny", [WHOLE, TINY], ids=["published", "cut"])
def test_forward_is_the_references(tiny):
    """`llama.forward` over the 40-entry published pattern (a lead of two
    conv layers, nine periods, half a period at the end) and over the cut's:
    the reference's logits at every position; and no layer of `init_params`
    carries another shape's weights."""
    cfg = serve_lfm2.model_config(tiny)
    weights = ref.init(*seed_words(2 ** 31 + 35), tiny)
    shapes = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    assert jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), shapes) == \
        jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), weights)
    specs = llama.param_specs(cfg)
    assert jax.tree_util.tree_structure(shapes) == \
        jax.tree_util.tree_structure(
            specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    for stack, (operator, routed, n) in zip(weights["runs"], cfg.runs):
        assert stack["attn_norm"].shape[0] == n
        assert ("conv_in" in stack) == (operator == "conv")
        assert ("wq" in stack) == (operator == "attention")
        assert ("router" in stack) == routed == ("wg" not in stack)
    assert weights["experts"]["wg"].shape == (cfg.expert_layers, 8, 64, 48)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, 96)
    got = jax.jit(lambda p, t: llama.forward(
        p, t, cfg, tp_axis=None, cp_axis=None, remat=False))(weights, tokens)
    reference = jax.jit(lambda p, t, precision="float32": ref.row_logits(
        p, t, jnp.arange(24), tiny, precision), static_argnums=2)
    for row in range(2):
        want = reference(weights, tokens[row])
        assert float(jnp.max(jnp.abs(got[row] - want))) < TIGHT
    if tiny is TINY:        # what the tolerance refuses: bf16 operands
        low = reference(weights, tokens[1], "bfloat16")
        assert float(jnp.max(jnp.abs(low - want))) > 50 * TIGHT


def test_generate_decodes_what_the_reference_puts_first(params):
    """`generate`: prefill, then every step from K, V and the conv states.
    Each token is the float32 reference's first on the sequence so far (a gap
    of 0, and under LIMIT / 10 where two logits tie to rounding)."""
    prompts = jax.random.randint(jax.random.PRNGKey(2), (2, 7), 0, 96)
    out = np.asarray(gen.generate(params, prompts, CFG, 9))
    for row in range(2):
        gaps = reference_gaps(
            params, jnp.asarray(np.pad(out[row], (0, SEQ - 16))),
            6 + jnp.arange(12), jnp.asarray(np.pad(out[row, 7:], (0, 3))))
        assert float(jnp.max(gaps[:9])) < LIMIT / 10


def test_the_conv_operator_is_one_function_in_two_forms(params):
    """`llama.short_conv` over a whole sequence, and one position at a time
    from the state the position before left: the same outputs (float32
    round-off of the two last products apart) and the same state, which is
    `u` at the last two positions, zeros before position 0."""
    lp = jax.tree_util.tree_map(lambda w: w[1], params["runs"][2])
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 11, 64))
    whole, kept = llama.short_conv(h, lp, CFG, llama.conv_window(CFG))
    state, steps = None, []
    for t in range(11):
        y, state = llama.short_conv(h[:, t:t + 1], lp, CFG,
                                    llama.conv_window(CFG, state=state))
        steps.append(y)
    np.testing.assert_allclose(jnp.concatenate(steps, axis=1), whole,
                               atol=1e-6)
    np.testing.assert_array_equal(state, kept)
    # the state under a padded bucket is taken at the true length
    for length in (1, 2, 7):
        _, at = llama.short_conv(h, lp, CFG,
                                 llama.conv_window(CFG, length=length))
        _, want = llama.short_conv(h[:, :length], lp, CFG,
                                   llama.conv_window(CFG))
        np.testing.assert_array_equal(at, want)
    assert not np.asarray(at[:, 0] == 0).all()
    _, short = llama.short_conv(h, lp, CFG, llama.conv_window(CFG, length=1))
    assert (np.asarray(short[:, 0]) == 0).all()
    # the equations, by hand: c_t = sum_j w[:, j] u_{t - 2 + j}
    gate_in, gate_out, x = jnp.split(h @ lp["conv_in"], 3, axis=-1)
    u = jnp.pad(gate_in * x, ((0, 0), (2, 0), (0, 0)))
    c = sum(lp["conv_w"][:, j] * u[:, j:j + 11] for j in range(3))
    np.testing.assert_allclose((gate_out * c) @ lp["conv_out"], whole,
                               atol=1e-5)


# --------------------------------------------------------------- serving


def test_served_tokens_lie_within_the_limit_of_the_reference(params):
    work = jobs()
    engine = engine_for(params)
    results = serve_all(engine, work)
    cache = engine.scheduler.cache
    # pages of the two attention layers alone, the heads side by side; the
    # seven conv layers' state beside them
    assert cache.k_pages.shape == (2, 28, 4, 128)
    assert cache.conv_state.shape == (7, 2, 3, 64)
    assert cache.hbm_bytes() == cache.page_pool_bytes() + 7 * 2 * 3 * 64 * 4
    assert engine.scheduler.decode_retraces() == 0
    assert widest_gap(params, work, results) < LIMIT / 10


def swapped(params, fault):
    """The engine's weights with a conv operator's fault planted."""
    def plant(stack):
        if "conv_in" not in stack:
            return stack
        w_in, taps = stack["conv_in"], stack["conv_w"]
        if fault == "b_and_c_swapped":
            b, c, x = jnp.split(w_in, 3, axis=-1)
            w_in = jnp.concatenate([c, b, x], axis=-1)
        else:
            taps = taps[..., ::-1]
        return {**stack, "conv_in": w_in, "conv_w": taps}
    return {**params, "runs": tuple(map(plant, params["runs"]))}


def bias_in_the_weight(x, router, bias=None, *, top_k, route_scale=1.0,
                       norm_eps=1e-20, **_):
    s = jax.nn.sigmoid(x.astype(jnp.float32) @ router.astype(jnp.float32))
    w, idx = jax.lax.top_k(s + bias, top_k)
    return w / (w.sum(-1, keepdims=True) + norm_eps) * route_scale, \
        idx.astype(jnp.int32)


@pytest.mark.parametrize("fault", [
    "state_at_the_buckets_end", "b_and_c_swapped", "taps_reversed",
    "bias_in_the_weight", "no_epsilon"])
def test_planted_faults_fail_the_limit(params, fault, monkeypatch):
    cfg, weights = CFG, params
    if fault == "state_at_the_buckets_end":
        true = gen._prefill_attend
        monkeypatch.setattr(gen, "_prefill_attend",
                            lambda lp, cfg, length=None: true(lp, cfg))
    elif fault == "bias_in_the_weight":
        monkeypatch.setattr(moe, "route", bias_in_the_weight)
    elif fault == "no_epsilon":
        cfg = dataclasses.replace(CFG, route_norm_eps=1e-20)
    else:
        weights = swapped(params, fault)
    work = jobs()
    results = serve_all(engine_for(weights, cfg), work)
    gap = widest_gap(params, work, results)
    if fault == "no_epsilon":
        # a part in four million of a weight: under every tolerance, as the
        # reference's docstring says; kept because the source has it
        assert gap < LIMIT / 10
    else:
        assert gap > LIMIT


ROWS, WIDTH = 5, 6                         # tables [5, 6]: 30 slots


def one_step(step, params, placed, others_active):
    """One decode step in which two requests (at positions 9 and 21) sit in
    the table rows `placed`, three other rows beside them active or not: each
    one's next token, what it wrote of K and V in both cache layers and the
    conv state it left; and the whole state buffer."""
    shape = (CFG.cache_layers, ROWS * WIDTH + 1, PAGE, 128)
    k0 = jax.random.normal(jax.random.PRNGKey(1), shape)
    v0 = jax.random.normal(jax.random.PRNGKey(2), shape)
    s0 = jax.random.normal(jax.random.PRNGKey(3), (7, 2, ROWS, 64))
    pos = np.array([9, 21, 5, 14, 23], np.int32)
    tokens = np.array([7, 50, 3, 90, 11], np.int32)
    tables = np.arange(ROWS * WIDTH, dtype=np.int32).reshape(ROWS, WIDTH)
    source = np.zeros(ROWS, int)
    source[list(placed)] = 0, 1
    source[[at for at in range(ROWS) if at not in placed]] = 2, 3, 4
    active = np.full(ROWS, others_active)
    active[list(placed)] = True
    # every row starts from its own request's state, wherever it sits
    s_in = s0[:, :, source]
    nxt, k1, v1, s1 = step(params, {}, k0, v0, s_in, *(
        jnp.asarray(a[source]) for a in (tokens, tables, pos)),
        jnp.asarray(active), jnp.zeros(ROWS, bool), jnp.zeros(ROWS, jnp.int32))
    wrote = [(slice(None), tables[r, pos[r] // PAGE], pos[r] % PAGE)
             for r in (0, 1)]
    rows = [(int(nxt[at]), np.asarray(k1[w]), np.asarray(v1[w]),
             np.asarray(s1[:, :, at])) for at, w in zip(placed, wrote)]
    idle = [at for at in range(ROWS) if not active[at]]
    np.testing.assert_array_equal(np.asarray(s1[:, :, idle]),
                                  np.asarray(s_in[:, :, idle]))
    return rows


def test_a_row_gets_the_same_bits_in_any_batch(params, monkeypatch):
    """What one compiled step gives a row (its token, its K and V, its conv
    state) is made of the row's own, the same to the bit alone in the batch
    and among other rows, in whichever slot it sits; a row that is not
    active leaves its slot's state as it was."""
    monkeypatch.setattr(sched, "LIST_CHUNK", 4)
    step = jax.jit(sched.build_decode_step(CFG, PAGE))
    want = one_step(step, params, (0, 1), False)
    for placed, others_active in (((0, 1), True), ((4, 2), True),
                                  ((3, 0), False)):
        for w, g in zip(want, one_step(step, params, placed, others_active)):
            assert w[0] == g[0]
            for a, b in zip(w[1:], g[1:]):
                np.testing.assert_array_equal(a, b)
    assert step._cache_size() == 1


def test_a_slot_another_row_has_left_gives_the_same_tokens_and_state(params):
    """A row seated in a slot whose last row's state still lies there reads
    none of it: the same tokens, and the same state to the bit at its end,
    as in an engine in which it is the first."""
    work = jobs(3, ((1, 8), (2, 8)))

    def final_state(engine):
        seen = {}
        for prompt, new in work:
            engine.submit(prompt, new)
        while engine.pending:
            engine.step()
            for slot, req in enumerate(engine.scheduler.slots):
                if req is not None and req.rid >= first:
                    seen[req.rid - first] = slot
        state = np.asarray(engine.scheduler.cache.conv_state)
        return [engine.results[first + i]["tokens"] for i in range(2)], [
            state[:, :, seen[i]] for i in range(2)]

    first = 0
    want_tokens, want_state = final_state(engine_for(params, max_batch=2))
    used = engine_for(params, max_batch=2)
    serve_all(used, jobs(4, ((19, 12), (18, 12), (8, 9))))
    assert float(jnp.max(jnp.abs(used.scheduler.cache.conv_state))) > 0
    first = 3
    got_tokens, got_state = final_state(used)
    assert got_tokens == want_tokens
    for a, b in zip(want_state, got_state):
        np.testing.assert_array_equal(a, b)


def test_dump_and_resume_restore_the_conv_state_bit_for_bit(params, tmp_path):
    work = jobs(5)
    want = serve_all(engine_for(params), work)
    d = str(tmp_path / "dump")
    engine = engine_for(params, fault_plan=FaultPlan.parse("seed=1,preempt@3"),
                        dump_dir=d)
    with pytest.raises(Preempted):
        serve_all(engine, work)
    sch = engine.scheduler
    inflight = {req.rid: slot for slot, req in enumerate(sch.slots)
                if req is not None}
    assert len(inflight) > 1
    with np.load(os.path.join(d, _PAGES_FILE)) as arrays:
        dumped = {rid: arrays[f"s_{rid}"] for rid in inflight}
        assert all(f"k_{rid}" in arrays for rid in inflight)
    for rid, slot in inflight.items():
        assert dumped[rid].shape == (7, 2, 64)
        np.testing.assert_array_equal(
            dumped[rid], np.asarray(sch.cache.conv_state[:, :, slot]))
        assert np.abs(dumped[rid]).max() > 0
    resumed = ServingEngine.resume(d, params, CFG,
                                   registry=obs.MetricRegistry())
    for slot, req in enumerate(resumed.scheduler.slots):
        if req is not None:
            np.testing.assert_array_equal(
                dumped[req.rid],
                np.asarray(resumed.scheduler.cache.conv_state[:, :, slot]))
    assert resumed.run() == want
    assert resumed.scheduler.decode_retraces() == 0
    with pytest.raises(ValueError, match="conv state"):
        resumed.scheduler.import_request(
            {"rid": 99, "prompt": [1], "max_new_tokens": 2, "npages": 1,
             "tokens": [3], "pos": 1}, None, None)


def test_defrag_moves_pages_and_leaves_the_state_alone(params):
    engine = engine_for(params)
    work = jobs(6)
    for prompt, new in work:
        engine.submit(prompt, new)
    for _ in range(6):
        engine.step()
    engine.scheduler.land()
    before = np.asarray(engine.scheduler.cache.conv_state)
    engine.scheduler.cache.defrag()
    np.testing.assert_array_equal(
        before, np.asarray(engine.scheduler.cache.conv_state))


def test_the_budget_counts_attention_layers_and_the_state():
    assert kv_cache.page_hbm_bytes(CFG, PAGE) == 2 * 2 * PAGE * 4 * 32 * 4
    assert kv_cache.state_hbm_bytes(CFG, 3) == 7 * 3 * 2 * 64 * 4
    assert kv_cache.state_hbm_bytes(llama.tiny(), 3) == 0
    plain = kv_cache.derive_page_budget(
        CFG, PAGE, hbm_bytes=10 ** 6, watermark_bytes=0, priors={})
    beside = kv_cache.derive_page_budget(
        CFG, PAGE, hbm_bytes=10 ** 6, watermark_bytes=0, priors={},
        state_rows=32)
    assert beside.state_bytes == 7 * 32 * 2 * 64 * 4 == 114_688
    assert plain.usable_bytes - beside.usable_bytes == beside.state_bytes
    assert beside.pages == beside.usable_bytes // beside.page_bytes < \
        plain.pages
    # heads narrower than a tile lie side by side where they fill tiles
    assert kv_cache.page_dims(CFG) == (128,)
    assert kv_cache.page_dims(llama.tiny()) == (2, 16)
    assert kv_cache.page_dims(llama.flagship_0p9b()) == (8, 128)
    with pytest.raises(ValueError, match="max_batch"):
        kv_cache.PagedKVCache(CFG, 8, PAGE)


def test_the_spans_and_gauges_say_what_the_state_costs(params):
    tracer = SpanTracer()
    previous = set_tracer(tracer)
    registry = obs.MetricRegistry()
    try:
        engine = engine_for(params, registry=registry)
        serve_all(engine, jobs(7))
        spans = tracer.completed()
    finally:
        set_tracer(previous)
    row = 7 * 2 * 64 * 4
    by_id = {s.id: s for s in spans}
    writes = [s for s in spans if s.name == "serving/write_state"]
    assert len(writes) == len(JOBS)
    for s in writes:
        assert (s.args["conv_layers"], s.args["bytes"]) == (7, row)
        assert by_id[s.parent].name == "serving/admit"
    for s in spans:
        if s.name == "serving/write_prompt":
            assert s.args["cache_layers"] == 2
        if s.name == "serving/admit":
            assert (s.args["conv_layers"], s.args["cache_layers"],
                    s.args["layer_passes"], s.args["expert_layers"],
                    s.args["experts_held"]) == (7, 2, 9, 8, 8)
    decodes = [s.args for s in spans if s.name == "serving/decode"]
    assert decodes and all(
        a["conv_layers"] == 7 and a["state_bytes"] == a["rows"] * row
        for a in decodes)
    assert sum(a["expert_tokens"] for a in decodes) == 4 * 8 * sum(
        a["rows"] for a in decodes[:-1])     # every expert is held
    gauges = {r["name"]: r["value"] for r in registry.to_records()
              if r.get("name", "").startswith("serving/")
              and r.get("name", "").endswith("_bytes")}
    cache = engine.scheduler.cache
    assert gauges == {"serving/page_pool_bytes": cache.page_pool_bytes(),
                      "serving/conv_state_bytes": 3 * row}


def test_what_is_not_served_or_trained_is_refused_by_name(params):
    with pytest.raises(NotImplementedError, match="conv layers with fp8"):
        sched.build_decode_step(CFG, PAGE, weight_mode="fp8")
    alone = dataclasses.replace(CFG, layer_types=("conv",) * 9)
    with pytest.raises(NotImplementedError, match="conv layers alone"):
        sched.build_prefill(alone, 16)
    with pytest.raises(NotImplementedError, match="looped stack with conv"):
        dataclasses.replace(CFG, num_passes=2)
    with pytest.raises(ValueError, match="capacity-dropped"):
        llama.tiny(num_layers=2, layer_types=("conv", "full_attention"),
                   num_experts=4)
    with pytest.raises(ValueError, match="unknown layer type"):
        llama.tiny(layer_types=("conv", "mamba"))
    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match="two layer shapes"):
        llama.loss_fn(params, (tokens, tokens), CFG, tp_axis=None,
                      cp_axis=None)
    with pytest.raises(NotImplementedError, match="scan_passes"):
        llama.run_layers(None, None, CFG, None)


def test_a_dense_stack_with_conv_layers_is_served_as_generate_decodes_it():
    """Conv layers without the experts: through the engine's state buffer
    and through `generate`'s: the same greedy tokens. A bias a channel,
    which no configuration or reference states, is refused."""
    with pytest.raises(ValueError, match="conv_bias"):
        llama.tiny(conv_bias=True)
    cfg = llama.tiny(num_layers=4, layer_types=(
        "conv", "full_attention", "conv", "full_attention"))
    weights = llama.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    work = [(rng.integers(0, cfg.vocab_size, size=p).astype(np.int32), new)
            for p, new in ((1, 8), (12, 7), (19, 9))]
    engine = ServingEngine(weights, cfg, page_size=4, max_batch=2,
                           num_pages=32, max_prompt_len=24, max_new_cap=12,
                           registry=obs.MetricRegistry())
    results = serve_all(engine, work)
    assert engine.scheduler.cache.k_pages.shape == (2, 33, 4, 2, 16)
    for rid, (prompt, new) in enumerate(work):
        want = np.asarray(gen.generate(weights, jnp.asarray(prompt)[None],
                                       cfg, new))[0, len(prompt):]
        assert results[rid]["tokens"] == [int(t) for t in want]
