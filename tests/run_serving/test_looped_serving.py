"""A looped llama stack through the serving engine (ISSUE 29): prefill, page
write, paged decode over `[T*L]` cache layers and several rows of different
lengths, judged as the benchmark judges a cell: by the widest gap of a served
token's logit below the best of the plain reference
(`perfbench/references/ouro_looped.py`) on the same seeded float32 weights.
Planted faults fail that limit. The cache, its transfers, the dump and the
span ring all count cache layers."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import observability as obs
from apex_tpu.models import llama
from apex_tpu.observability import SpanTracer, set_tracer
from apex_tpu.resilience import FaultPlan
from apex_tpu.resilience.loop import Preempted
from apex_tpu.serving import ServingEngine, derive_page_budget, page_hbm_bytes
from apex_tpu.serving import kv_cache as kvc
from apex_tpu.serving import scheduler as sched_mod
from perfbench.references import ouro_looped as ref
from perfbench.references.common import seed_words

TINY = {"hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
        "num_hidden_layers": 2, "vocab_size": 256, "rms_norm_eps": 1e-6,
        "rope_theta": 1000000, "total_ut_steps": 4,
        "tie_word_embeddings": False, "torch_dtype": "float32"}
CFG = llama.LlamaConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=2,
    num_heads=4, num_kv_heads=4, max_seq_len=256, rope_theta=1e6,
    rms_eps=1e-6, dtype=jnp.float32, num_passes=4, sandwich_norm=True)
LIMIT = 0.01                      # the tiny cells' `logit_gap_limit`
JOBS = ((3, 9), (8, 12), (11, 6), (5, 12), (8, 4), (20, 10), (16, 12))


@pytest.fixture(scope="module")
def params():
    return ref.init(*seed_words(2 ** 31 + 29), TINY)


def jobs(seed=0):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, 256, size=p).astype(np.int32), new)
            for p, new in JOBS]


def engine_for(params, cfg=CFG, **kw):
    kw.setdefault("registry", obs.MetricRegistry())
    return ServingEngine(params, cfg, page_size=8, max_batch=3, num_pages=18,
                         max_prompt_len=24, max_new_cap=12, **kw)


def serve_all(engine, work):
    for prompt, new in work:
        engine.submit(prompt, new)
    return engine.run()


def widest_gap(params, work, results):
    """As `runners/serve.check_served`: one reference forward over each
    prompt with its served tokens."""
    widest = 0.0
    for rid, (prompt, new) in enumerate(work):
        served = np.asarray(results[rid]["tokens"], np.int32)
        assert len(served) == new
        seq = jnp.asarray(np.concatenate([prompt, served[:-1]]))
        rows = len(prompt) - 1 + jnp.arange(new)
        logits = ref.row_logits(params, seq, rows, TINY)
        widest = max(widest, float(jnp.max(
            ref.served_gaps(logits, jnp.asarray(served)))))
    return widest


def test_served_tokens_lie_within_the_limit_of_the_reference(params):
    work = jobs()
    engine = engine_for(params)
    results = serve_all(engine, work)
    assert engine.scheduler.cache.k_pages.shape == (8, 19, 8, 4, 16)
    assert engine.scheduler.decode_retraces() == 0
    assert widest_gap(params, work, results) < LIMIT / 10


def reuse_first_pass_cache(x, params, cfg, layer_fn, shared, per_layer=None):
    """`llama.scan_passes` with the fault planted: every pass of layer l
    reads and writes cache layer l, the first pass's (prefill untouched).
    The decode step's `per_layer` is each step's cache layer."""
    if per_layer is not None:
        per_layer = per_layer % cfg.num_layers
    return TRUE_SCAN_PASSES(x, params, cfg, layer_fn, shared, per_layer)


TRUE_SCAN_PASSES = llama.scan_passes


@pytest.mark.parametrize("fault", ["three_passes", "first_pass_cache",
                                   "no_post_norms"])
def test_planted_faults_fail_the_limit(params, fault, monkeypatch):
    cfg = CFG
    if fault == "three_passes":
        cfg = dataclasses.replace(CFG, num_passes=3)
    elif fault == "no_post_norms":
        cfg = dataclasses.replace(CFG, sandwich_norm=False)
    else:
        monkeypatch.setattr(sched_mod._llama, "scan_passes",
                            reuse_first_pass_cache)
    work = jobs()
    results = serve_all(engine_for(params, cfg), work)
    assert widest_gap(params, work, results) > LIMIT


def test_a_page_and_the_budget_count_cache_layers():
    plain = dataclasses.replace(CFG, num_passes=1)
    assert page_hbm_bytes(CFG, 8) == 2 * 8 * 8 * 4 * 16 * 4
    assert page_hbm_bytes(CFG, 8) == 4 * page_hbm_bytes(plain, 8)
    kw = dict(hbm_bytes=page_hbm_bytes(CFG, 8) * 100, watermark_bytes=0,
              priors={}, safety=1.0)
    assert derive_page_budget(CFG, 8, **kw).pages == 100
    assert derive_page_budget(plain, 8, **kw).pages == 400
    # Ouro-2.6B's page of 64 tokens: 96 MiB, 1.5 MiB a cached token
    ouro = llama.LlamaConfig(vocab_size=49152, hidden_size=2048,
                             intermediate_size=5632, num_layers=48,
                             num_heads=16, num_kv_heads=16, num_passes=4,
                             sandwich_norm=True)
    assert page_hbm_bytes(ouro, 64) == 96 * 2 ** 20
    assert page_hbm_bytes(ouro, 1) == 1_572_864


def test_write_gather_restore_round_trip_over_all_cache_layers():
    cache = kvc.PagedKVCache(CFG, num_pages=6, page_size=4)
    assert cache.layers == 8 and cache.k_pages.shape == (8, 7, 4, 4, 16)
    assert cache.hbm_bytes() == 7 * page_hbm_bytes(CFG, 4)
    ks = jax.random.normal(jax.random.PRNGKey(1), (8, 8, 4, 16))
    vs = jax.random.normal(jax.random.PRNGKey(2), (8, 8, 4, 16))
    pages = cache.alloc.alloc(2, "a")
    cache.write_prompt(pages, ks, vs)
    k, v = cache.gather_pages(pages)
    assert k.shape == (8, 2, 4, 4, 16)
    np.testing.assert_array_equal(k.reshape(8, 8, 4, 16), np.asarray(ks))
    other = kvc.PagedKVCache(CFG, num_pages=6, page_size=4)
    there = other.alloc.alloc(2, "b")
    other.restore_pages(there, k, v)
    back_k, back_v = other.gather_pages(there)
    np.testing.assert_array_equal(back_k, k)
    np.testing.assert_array_equal(back_v, v)
    with pytest.raises(ValueError, match="do not fit this cache"):
        other.restore_pages(there, k[:2], v[:2])       # a plain model's dump
    with pytest.raises(ValueError):
        cache.write_prompt(pages, ks[:, :4], vs[:, :4])


def test_the_page_write_is_in_place_under_donation():
    """`write_prompt` and `restore_pages` hand their buffers over: no second
    whole K or V buffer lives while a prompt is written (with Ouro-2.6B's
    cache that third copy is 1.92 GiB of a 15.75 GiB chip)."""
    cache = kvc.PagedKVCache(CFG, num_pages=6, page_size=4)
    ks = jnp.ones((8, 8, 4, 16))
    before = (cache.k_pages, cache.v_pages)
    cache.write_prompt(cache.alloc.alloc(2, "a"), ks, 2 * ks)
    assert all(b.is_deleted() for b in before)
    k, v = cache.gather_pages([0, 1])
    assert (k == 1).all() and (v == 2).all()
    before = (cache.k_pages, cache.v_pages)
    cache.restore_pages([3, 4], 3 * k, 3 * v)
    assert all(b.is_deleted() for b in before)
    assert (cache.gather_pages([3, 4])[0] == 3).all()
    assert (cache.gather_pages([0, 1])[1] == 2).all()      # untouched
    jaxpr = jax.make_jaxpr(kvc._serving_write_pages)(
        cache.k_pages, jnp.zeros((2,), jnp.int32), ks)
    assert jaxpr.jaxpr.eqns[0].params["donated_invars"][0] is True


@pytest.mark.parametrize("chunk", [sched_mod.LIST_CHUNK, 4])
def test_dump_then_resume_is_bit_identical(params, tmp_path, chunk,
                                           monkeypatch):
    """Also with the decode step's list of live pages walked in chunks of 4
    under tables of 15 slots, as the benchmark's tables are several chunks."""
    monkeypatch.setattr(sched_mod, "LIST_CHUNK", chunk)
    work = jobs(seed=5)
    want = serve_all(engine_for(params), work)
    d = str(tmp_path / "dump")
    engine = engine_for(params, fault_plan=FaultPlan.parse("seed=1,preempt@6"),
                        dump_dir=d)
    with pytest.raises(Preempted):
        serve_all(engine, work)
    with open(os.path.join(d, "state.json")) as f:
        inflight = json.load(f)["inflight"]
    assert inflight
    with np.load(os.path.join(d, "kv_pages.npz")) as pages:
        for rec in inflight:
            assert pages[f"k_{rec['rid']}"].shape == (
                8, rec["npages"], 8, 4, 16)
    resumed = ServingEngine.resume(d, params, CFG,
                                   registry=obs.MetricRegistry())
    assert resumed.run() == want
    assert resumed.scheduler.decode_retraces() == 0


@pytest.fixture
def tracer():
    t = SpanTracer(capacity=4096)
    prev = set_tracer(t)
    yield t
    set_tracer(prev)


@pytest.mark.parametrize("cfg,layer_passes", [(CFG, 8), (llama.tiny(), 2)])
def test_spans_carry_layer_passes_and_cache_layers(params, tracer, cfg,
                                                   layer_passes):
    """A plain model writes `layer_passes` = L; a looped one T x L."""
    weights = params if cfg is CFG else llama.init_params(
        jax.random.PRNGKey(0), cfg)
    serve_all(engine_for(weights, cfg), jobs()[:3])
    spans = tracer.completed()
    for name in ("serving/decode", "serving/admit"):
        records = [s for s in spans if s.name == name]
        assert records
        assert {s.args["layer_passes"] for s in records} == {layer_passes}
        assert {s.args["cache_layers"] for s in records} == {layer_passes}
    writes = [s for s in spans if s.name == "serving/write_prompt"]
    assert {s.args["cache_layers"] for s in writes} == {layer_passes}
    assert all(s.args["pages"] >= 1 for s in writes)
