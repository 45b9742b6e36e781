"""Paged KV cache (apex_tpu/serving/kv_cache.py): allocator
accounting, the calibrated page-budget derivation, and the
write/gather/restore/defrag data paths the scheduler and the
emergency dump depend on (ISSUE 20)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import llama
from apex_tpu.serving import kv_cache as kvc


def _cfg():
    return llama.tiny()


# ---------------------------------------------------------- allocator


def test_allocator_alloc_free_accounting():
    a = kvc.PageAllocator(6)
    assert a.num_free == 6 and a.num_used == 0
    p1 = a.alloc(2, owner="r1")
    p2 = a.alloc(3, owner="r2")
    assert sorted(p1 + p2) == [0, 1, 2, 3, 4]
    assert a.num_free == 1 and a.num_used == 5
    assert a.pages_of("r1") == p1
    assert a.can_alloc(1) and not a.can_alloc(2)
    assert a.free_owner("r1") == 2
    assert a.num_free == 3
    assert a.pages_of("r1") == []
    # freed pages are reusable and accounting stays exact
    p3 = a.alloc(3, owner="r3")
    assert a.num_free == 0
    assert sorted(a.live_pages()) == sorted(p2 + p3)


def test_allocator_exhaustion_is_loud():
    a = kvc.PageAllocator(2)
    a.alloc(2, owner="r1")
    with pytest.raises(RuntimeError, match="out of KV pages"):
        a.alloc(1, owner="r2")
    with pytest.raises(ValueError):
        a.alloc(0, owner="r3")
    with pytest.raises(ValueError):
        kvc.PageAllocator(0)


# ------------------------------------------------------------- budget


def test_page_hbm_bytes_formula():
    cfg = _cfg()
    got = kvc.page_hbm_bytes(cfg, page_size=8)
    want = (2 * cfg.num_layers * 8 * cfg.num_kv_heads * cfg.head_dim
            * jnp.dtype(cfg.dtype).itemsize)
    assert got == want


def test_derive_page_budget_math_with_overrides():
    cfg = _cfg()
    page_bytes = kvc.page_hbm_bytes(cfg, page_size=8)
    priors = {"backend": "cpu", "default_ratio": 1.5,
              "priors": {"serving_decode_step": {"ratio": 2.0}}}
    b = kvc.derive_page_budget(cfg, 8, hbm_bytes=page_bytes * 100,
                               watermark_bytes=page_bytes * 10,
                               priors=priors, safety=0.5)
    # usable = 100p * 0.5 - 10p = 40p; effective page cost = 2.0p
    assert b.usable_bytes == page_bytes * 40
    assert b.ratio == 2.0
    assert b.pages == 20
    assert b.page_bytes == page_bytes
    # no serving-specific prior -> the document default prices the page
    b2 = kvc.derive_page_budget(cfg, 8, hbm_bytes=page_bytes * 100,
                                watermark_bytes=0,
                                priors={"backend": "cpu", "priors": {},
                                        "default_ratio": 1.5},
                                safety=1.0)
    assert b2.ratio == 1.5
    assert b2.pages == int(page_bytes * 100
                           // int(np.ceil(page_bytes * 1.5)))
    # a ratio measured on ANOTHER backend is not applied: one below 1
    # would add pages that do not fit on this one
    b3 = kvc.derive_page_budget(cfg, 8, hbm_bytes=page_bytes * 100,
                                watermark_bytes=0,
                                priors=dict(priors, backend="tpu"),
                                safety=1.0)
    assert b3.ratio == 1.0 and b3.pages == 100


def test_derive_page_budget_watermark_floor_and_safety_validation():
    cfg = _cfg()
    page_bytes = kvc.page_hbm_bytes(cfg, page_size=8)
    b = kvc.derive_page_budget(
        cfg, 8, hbm_bytes=page_bytes * 4,
        watermark_bytes=page_bytes * 50,
        priors={"priors": {}, "default_ratio": 1.0})
    assert b.usable_bytes == 0 and b.pages == 0
    with pytest.raises(ValueError, match="safety"):
        kvc.derive_page_budget(cfg, 8, hbm_bytes=1, watermark_bytes=0,
                               priors={"priors": {},
                                       "default_ratio": 1.0},
                               safety=1.5)


def test_derive_page_budget_live_tier_defaults():
    """With no overrides, the budget reads the real memory tier
    (device_hbm_bytes + committed priors) and lands a positive page
    count for the tiny config on any host."""
    b = kvc.derive_page_budget(_cfg(), 8)
    assert b.pages > 0
    assert b.ratio > 0
    assert b.hbm_bytes > b.page_bytes


# --------------------------------------------------------- data paths


def _fill(cache, pages, seed):
    """write_prompt a recognizable pattern; returns the [L,S,nkv,d]
    host arrays written."""
    cfg = cache.cfg
    s = len(pages) * cache.page_size
    rng = np.random.default_rng(seed)
    ks = rng.standard_normal(
        (cfg.num_layers, s, cfg.num_kv_heads, cfg.head_dim)).astype(
        np.float32)
    vs = rng.standard_normal(ks.shape).astype(np.float32)
    cache.write_prompt(pages, jnp.asarray(ks), jnp.asarray(vs))
    return ks, vs


def test_write_gather_restore_roundtrip():
    cfg = _cfg()
    cache = kvc.PagedKVCache(cfg, num_pages=6, page_size=4)
    pages = cache.alloc.alloc(2, owner=0)
    ks, vs = _fill(cache, pages, seed=0)
    k, v = cache.gather_pages(pages)
    assert k.shape == (cfg.num_layers, 2, 4, cfg.num_kv_heads,
                       cfg.head_dim)
    np.testing.assert_array_equal(
        k.reshape(cfg.num_layers, 8, cfg.num_kv_heads, cfg.head_dim), ks)
    # wipe + restore must be bit-exact (the resume contract)
    cache.k_pages = jnp.zeros_like(cache.k_pages)
    cache.v_pages = jnp.zeros_like(cache.v_pages)
    cache.restore_pages(pages, k, v)
    k2, v2 = cache.gather_pages(pages)
    np.testing.assert_array_equal(k2, k)
    np.testing.assert_array_equal(v2, v)


def test_write_prompt_length_mismatch_is_loud():
    cache = kvc.PagedKVCache(_cfg(), num_pages=4, page_size=4)
    pages = cache.alloc.alloc(1, owner=0)
    cfg = cache.cfg
    bad = jnp.zeros((cfg.num_layers, 6, cfg.num_kv_heads, cfg.head_dim))
    with pytest.raises(ValueError, match="prefill length"):
        cache.write_prompt(pages, bad, bad)


def test_trash_page_never_allocated():
    cache = kvc.PagedKVCache(_cfg(), num_pages=3, page_size=4)
    got = cache.alloc.alloc(3, owner=0)
    assert cache.trash_page == 3
    assert cache.trash_page not in got
    assert cache.k_pages.shape[1] == 4  # 3 real + 1 trash


def test_defrag_compacts_and_moves_data():
    cache = kvc.PagedKVCache(_cfg(), num_pages=8, page_size=4)
    a = cache.alloc
    a.alloc(2, owner="a")        # pages 0,1
    a.alloc(2, owner="b")        # pages 2,3
    a.alloc(2, owner="c")        # pages 4,5
    kb, vb = _fill(cache, a.pages_of("b"), seed=1)
    kc, vc = _fill(cache, a.pages_of("c"), seed=2)
    a.free_owner("a")
    a.free_owner("b")
    mapping = cache.defrag()
    # live pages 4,5 move to the front
    assert mapping == {4: 0, 5: 1}
    assert a.pages_of("c") == [0, 1]
    assert a.num_used == 2 and a.num_free == 6
    # the data followed its pages
    k, _ = cache.gather_pages(a.pages_of("c"))
    np.testing.assert_array_equal(
        k.reshape(kc.shape[0], -1, *kc.shape[2:]), kc)
    # already-compact cache is a no-op
    assert cache.defrag() == {}


def test_utilization_tracks_allocator():
    cache = kvc.PagedKVCache(_cfg(), num_pages=4, page_size=4)
    assert cache.utilization() == 0.0
    cache.alloc.alloc(1, owner=0)
    assert cache.utilization() == 0.25
    cache.alloc.free_owner(0)
    assert cache.utilization() == 0.0
