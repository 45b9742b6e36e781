"""The decode step's values (ISSUE 30): the step that carries the page buffers
through its layer scan and updates them in place gives the next tokens and
both buffers of a plain Python loop over the cache layers written here
(scatter, gather, attention, MLP), for a plain and a looped stack, with native
and fp8 weights, and with a slot that is not active. The tokens are the same;
the buffers are the same to float32 round-off outside the trash page, since
the step sums a row's softmax a page at a time (ISSUE 32) and the loop here
keeps `generate._decode_attention` over the whole table. Of its input buffers
the step changes the rows it writes and no other bit."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import generate as gen
from apex_tpu.models import llama
from apex_tpu.ops.precision import matmul_fp8
from apex_tpu.serving import scheduler as sched
from apex_tpu.transformer.functional.rope import apply_rotary_qk

PAGE, PAGES, ROWS = 4, 9, 4                     # page 9 is the trash page
STACKS = {"plain": llama.tiny(),
          "looped": dataclasses.replace(llama.tiny(), num_passes=4,
                                        sandwich_norm=True)}
# row 2 is not active: its table is trash and its position is stale
TABLES = np.array([[0, 1, 2], [3, 4, 9], [9, 9, 9], [5, 6, 7]], np.int32)
POS = np.array([9, 4, 6, 0], np.int32)
ACTIVE = np.array([True, True, False, True])
TOKENS = np.array([7, 200, 31, 5], np.int32)


def mm(x, w, scale):
    """The layer's gemm in either weight mode: a scale means fp8."""
    if scale is None:
        return jnp.matmul(x, w.astype(x.dtype))
    return matmul_fp8(x, w, jnp.float32(1.0), scale).astype(x.dtype)


def plain_loop(params, scales, cfg, k_pages, v_pages):
    """One decode step, a cache layer at a time, on whole arrays."""
    L, d, nkv = cfg.num_layers, cfg.head_dim, cfg.num_kv_heads
    pos = jnp.asarray(POS)
    page_idx = jnp.where(ACTIVE, TABLES[np.arange(ROWS), POS // PAGE], PAGES)
    off = POS % PAGE
    x = llama.embed(params, jnp.asarray(TOKENS)[:, None], cfg, tp_axis=None)
    for i in range(cfg.cache_layers):
        if i % L == 0 and i > 0:
            x = llama._rmsnorm(x, params["final_norm"], cfg.rms_eps)
        lp = jax.tree_util.tree_map(lambda w: w[i % L], params["layers"])
        sc = {name: s[i % L] for name, s in scales.items()}
        h = llama._rmsnorm(x, lp["attn_norm"], cfg.rms_eps)
        q = mm(h, lp["wq"], sc.get("wq")).reshape(ROWS, 1, cfg.num_heads, d)
        k = mm(h, lp["wk"], sc.get("wk")).reshape(ROWS, 1, nkv, d)
        v = mm(h, lp["wv"], sc.get("wv")).reshape(ROWS, 1, nkv, d)
        q, k = apply_rotary_qk(q, k, positions=pos[:, None],
                               base=cfg.rope_theta)
        k_pages = k_pages.at[i, page_idx, off].set(k[:, 0])
        v_pages = v_pages.at[i, page_idx, off].set(v[:, 0])
        kg = k_pages[i][TABLES].reshape(ROWS, -1, nkv, d)
        vg = v_pages[i][TABLES].reshape(ROWS, -1, nkv, d)
        o = gen._decode_attention(q, kg, vg, pos[:, None, None])
        x = x + llama.post_norm(mm(o.astype(x.dtype), lp["wo"], sc.get("wo")),
                                lp, "attn_post_norm", cfg)
        hm = llama._rmsnorm(x, lp["mlp_norm"], cfg.rms_eps)
        y = mm(jax.nn.silu(mm(hm, lp["wg"], sc.get("wg")))
               * mm(hm, lp["wu"], sc.get("wu")), lp["wd"], sc.get("wd"))
        x = x + llama.post_norm(y, lp, "mlp_post_norm", cfg)
    nxt = jnp.argmax(gen._logits(params, x, cfg)[:, 0], axis=-1)
    return jnp.where(ACTIVE, nxt.astype(jnp.int32), TOKENS), k_pages, v_pages


@pytest.mark.parametrize("mode", ["native", "fp8"])
@pytest.mark.parametrize("stack", ["plain", "looped"])
def test_the_carried_cache_equals_the_plain_loop(stack, mode):
    cfg = STACKS[stack]
    params = llama.init_params(jax.random.PRNGKey(30), cfg)
    scales = sched.fp8_weight_scales(params) if mode == "fp8" else {}
    shape = (cfg.cache_layers, PAGES + 1, PAGE, cfg.num_kv_heads,
             cfg.head_dim)
    k0 = jax.random.normal(jax.random.PRNGKey(1), shape, cfg.dtype)
    v0 = jax.random.normal(jax.random.PRNGKey(2), shape, cfg.dtype)
    want = jax.jit(plain_loop, static_argnums=2)(params, scales, cfg, k0, v0)
    step = jax.jit(sched.build_decode_step(cfg, PAGE, mode),
                   donate_argnums=(2, 3))
    *got, no_state = step(
        params, scales, k0 + 0, v0 + 0, None, jnp.asarray(TOKENS),
        jnp.asarray(TABLES), jnp.asarray(POS), jnp.asarray(ACTIVE),
        jnp.zeros(len(TOKENS), bool), jnp.zeros(len(TOKENS), jnp.int32))
    assert no_state is None
    for name, g, w in zip(("tokens", "k_pages", "v_pages"), got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    for name, g, w in zip(("k_pages", "v_pages"), got[1:], want[1:]):
        np.testing.assert_allclose(np.asarray(g)[:, :PAGES], np.asarray(
            w)[:, :PAGES], atol=1e-5, rtol=0, err_msg=name)
    assert int(got[0][2]) == TOKENS[2]           # passed through
    # what changed: one position a cache layer for each active row, and the
    # trash page, where the slot that is not active wrote
    for before, after in ((k0, got[1]), (v0, got[2])):
        changed = np.asarray(before != after).any(axis=(3, 4))
        expect = np.zeros_like(changed)
        for row in np.flatnonzero(ACTIVE):
            expect[:, TABLES[row, POS[row] // PAGE], POS[row] % PAGE] = True
        expect[:, PAGES, POS[2] % PAGE] = True
        np.testing.assert_array_equal(changed, expect)
