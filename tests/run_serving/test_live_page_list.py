"""The decode step reads the pages some row attends to (ISSUE 32).

`build_decode_step` lists, once a step, the tables' entries that hold a
position an active row attends to, and every layer walks that list in chunks
of `LIST_CHUNK` pages with a loop whose trip count follows the list. Here it
is held against the plain wording it replaced, kept below: every table gathered
whole (`kp[tables]`) and `generate._decode_attention` over all of it. Same next
tokens, same page buffers outside the trash page, for plain, grouped-query and
looped stacks, and for the lists that strain the chunking. And what a row gets
is its own to the bit: the same in any row of any batch, under any chunking."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import observability as obs
from apex_tpu.models import generate as gen
from apex_tpu.models import llama
from apex_tpu.serving import ServingEngine
from apex_tpu.serving import scheduler as sched

PAGE, ROWS, WIDTH = 4, 6, 5                # tables [6, 5]: 30 slots
PAGES = ROWS * WIDTH                       # page 30 is the trash page
# the step's last two arguments when every row's token is in `tokens`
NONE_FRESH = jnp.zeros(ROWS, bool), jnp.zeros(ROWS, jnp.int32)
STACKS = {
    "plain": llama.tiny(num_kv_heads=4),
    "grouped": llama.tiny(),               # 2 KV heads for 4
    "looped": dataclasses.replace(llama.tiny(), num_passes=3,
                                  sandwich_norm=True)}
# name: (chunk, positions, active). A row's live pages are pos // PAGE + 1.
CASES = {
    "mixed": (4, [9, 4, 6, 0, 17, 13], [1, 1, 0, 1, 1, 1]),
    "chunk_of_seven": (7, [19, 4, 6, 1, 17, 13], [1, 1, 1, 1, 0, 1]),
    "a_row_at_position_zero": (4, [0, 11, 0, 5, 0, 2], [1, 1, 1, 0, 1, 1]),
    "rows_on_page_boundaries": (4, [3, 4, 7, 8, 15, 16], [1, 1, 1, 1, 1, 1]),
    "one_active_row_of_many": (4, [5, 9, 14, 3, 7, 2], [0, 0, 1, 0, 0, 0]),
    # 3 + 1 + 2 + 1 + 1 = 8 live pages = two chunks of 4, none to spare
    "exactly_two_chunks": (4, [8, 3, 5, 2, 12, 1], [1, 1, 1, 1, 0, 1]),
    # and one entry more: 3 + 1 + 2 + 1 + 1 + 1 = 9
    "two_chunks_and_one_entry": (4, [8, 3, 5, 2, 0, 1], [1, 1, 1, 1, 1, 1]),
    "every_slot_live": (7, [19, 18, 17, 16, 19, 19], [1, 1, 1, 1, 1, 1]),
    "no_row_active": (4, [5, 9, 14, 3, 7, 2], [0, 0, 0, 0, 0, 0]),
    # capped at the tables' 30 slots: one chunk holds every list
    "chunk_larger_than_the_tables": (64, [9, 4, 6, 0, 17, 13],
                                     [1, 1, 0, 1, 1, 1])}


def whole_table_step(cfg):
    """The step before ISSUE 32: gather every table, mask by position."""

    def step(params, k_pages, v_pages, tokens, tables, pos, active):
        x = llama.embed(params, tokens[:, None], cfg, tp_axis=None)
        trash = k_pages.shape[1] - 1
        page_idx = jnp.where(active, jnp.take_along_axis(
            tables, (pos // PAGE)[:, None], axis=1)[:, 0], trash)
        off = pos % PAGE

        def body(carry, lp, i):
            h, kp, vp = carry

            def attend(q, k, v):
                kp1 = kp.at[i, page_idx, off].set(k[:, 0])
                vp1 = vp.at[i, page_idx, off].set(v[:, 0])
                kg = kp1[i][tables].reshape(ROWS, -1, *k.shape[2:])
                vg = vp1[i][tables].reshape(ROWS, -1, *v.shape[2:])
                o = gen._decode_attention(q, kg, vg, pos[:, None, None])
                return o.astype(q.dtype), (kp1, vp1)

            h, (kp, vp) = llama.block(h, lp, cfg, pos[:, None], attend)
            return (h, kp, vp), None

        (x, k_pages, v_pages), _ = llama.scan_passes(
            (x, k_pages, v_pages), params, cfg, body, params["layers"],
            jnp.arange(cfg.cache_layers))
        nxt = jnp.argmax(gen._logits(params, x, cfg)[:, 0], axis=-1)
        return (jnp.where(active, nxt.astype(jnp.int32), tokens), k_pages,
                v_pages)

    return jax.jit(step)


def batch(case):
    """Tables of distinct pages in a shuffled order for the active rows,
    trash for the others (whose positions are stale), and their tokens."""
    _, pos, active = CASES[case]
    rng = np.random.default_rng(sorted(CASES).index(case))
    active = np.asarray(active, bool)
    tables = rng.permutation(PAGES).astype(np.int32).reshape(ROWS, WIDTH)
    tables[~active] = PAGES
    tokens = rng.integers(0, 256, ROWS).astype(np.int32)
    return tokens, tables, np.asarray(pos, np.int32), active


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("stack", sorted(STACKS))
def test_the_list_step_is_the_whole_table_step(stack, case, monkeypatch):
    monkeypatch.setattr(sched, "LIST_CHUNK", CASES[case][0])
    cfg = STACKS[stack]
    params = llama.init_params(jax.random.PRNGKey(32), cfg)
    shape = (cfg.cache_layers, PAGES + 1, PAGE, cfg.num_kv_heads,
             cfg.head_dim)
    k0 = jax.random.normal(jax.random.PRNGKey(1), shape, cfg.dtype)
    v0 = jax.random.normal(jax.random.PRNGKey(2), shape, cfg.dtype)
    tokens, tables, pos, active = map(jnp.asarray, batch(case))
    want = whole_table_step(cfg)(params, k0, v0, tokens, tables, pos, active)
    step = jax.jit(sched.build_decode_step(cfg, PAGE), donate_argnums=(2, 3))
    got = step(params, {}, k0 + 0, v0 + 0, None, tokens, tables, pos, active,
               *NONE_FRESH)[:3]
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    for g, w in zip(got[1:], want[1:]):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_allclose(np.asarray(g[:, :PAGES]),
                                   np.asarray(w[:, :PAGES]), atol=1e-5, rtol=0)
    # a row that is not active passes its token through
    np.testing.assert_array_equal(np.asarray(got[0])[~np.asarray(active)],
                                  np.asarray(tokens)[~np.asarray(active)])


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_list_names_each_live_page_once_and_no_other(case, monkeypatch):
    monkeypatch.setattr(sched, "LIST_CHUNK", CASES[case][0])
    _, tables, pos, active = batch(case)
    chunk = min(CASES[case][0], tables.size)
    *entries, n_chunks = map(np.asarray, sched._live_page_list(
        jnp.asarray(tables), jnp.asarray(pos), jnp.asarray(active), PAGE,
        PAGES))
    assert all(a.shape[:2] == (-(-tables.size // chunk), chunk)
               for a in entries)
    row, slot, page, keys = (a.reshape(-1, *a.shape[2:]) for a in entries)
    live = [(r, c) for r in range(ROWS) if active[r]
            for c in range(pos[r] // PAGE + 1)]
    n = len(live)
    assert int(n_chunks) == -(-n // chunk)
    # what the decode record says the step read is what its loop walks
    assert sched.pages_read(n, tables.size) == int(n_chunks) * chunk
    assert [(r, r * WIDTH + c, tables[r, c]) for r, c in live] == list(
        zip(row[:n], slot[:n], page[:n]))
    assert (page[n:] == PAGES).all() and (slot[n:] == tables.size).all()
    assert not keys[n:].any()
    for e, (r, c) in enumerate(live):
        assert keys[e].tolist() == [c * PAGE + t <= pos[r]
                                    for t in range(PAGE)]


def test_the_step_reads_the_whole_chunks_of_its_list():
    assert sched.pages_read(3, sched.LIST_CHUNK) == sched.LIST_CHUNK
    assert sched.pages_read(3, 40) == sched.pages_read(40, 40) == 40
    assert sched.pages_read(0, 40) == sched.pages_read(0, 1056) == 0
    assert sched.pages_read(117, 1056) == 2 * sched.LIST_CHUNK
    assert sched.pages_read(128, 1056) == 2 * sched.LIST_CHUNK
    assert sched.pages_read(129, 1056) == 3 * sched.LIST_CHUNK


def two_rows_of(placed, others_active, step, cfg):
    """One step in which rows 0 and 4 of the mixed case (3 and 5 live pages)
    sit in the table rows `placed`, the four other rows active or not: each
    one's next token and what it wrote of K and V in every cache layer. A
    cache layer's row is made of the attention's output in the layer before,
    so these are the attention's bits."""
    shape = (cfg.cache_layers, PAGES + 1, PAGE, cfg.num_kv_heads,
             cfg.head_dim)
    k0 = jax.random.normal(jax.random.PRNGKey(1), shape, cfg.dtype)
    v0 = jax.random.normal(jax.random.PRNGKey(2), shape, cfg.dtype)
    tokens, tables, pos, _ = batch("every_slot_live")
    pos[:] = [9, 4, 6, 0, 17, 13]
    source = np.zeros(ROWS, int)
    source[list(placed)] = 0, 4
    source[[at for at in range(ROWS) if at not in placed]] = 1, 2, 3, 5
    active = np.full(ROWS, others_active)
    active[list(placed)] = True
    nxt, k1, v1, _ = step(params_of(cfg), {}, k0, v0, None, *(
        jnp.asarray(a[source]) for a in (tokens, tables, pos)),
        jnp.asarray(active), *NONE_FRESH)
    wrote = [(slice(None), tables[r, pos[r] // PAGE], pos[r] % PAGE)
             for r in (0, 4)]
    return [(int(nxt[at]), np.asarray(k1[w]), np.asarray(v1[w]))
            for at, w in zip(placed, wrote)]


def params_of(cfg):
    return llama.init_params(jax.random.PRNGKey(32), cfg)


@pytest.mark.parametrize("chunk", [1, 3, 4, 7, 64])
def test_a_row_gets_the_same_bits_in_any_batch(chunk, monkeypatch):
    """What one compiled step gives a row is made of the row's own query,
    pages and position: the same to the bit alone in the batch and among five
    other rows, in whichever table row it sits, and so wherever the list's
    chunks cut its pages apart. Preempt and resume rest on this."""
    monkeypatch.setattr(sched, "LIST_CHUNK", chunk)
    cfg = STACKS["looped"]
    step = jax.jit(sched.build_decode_step(cfg, PAGE))
    want = two_rows_of((0, 4), False, step, cfg)
    for placed, others_active in (((0, 4), True), ((5, 1), True),
                                  ((3, 2), False), ((2, 5), True)):
        got = two_rows_of(placed, others_active, step, cfg)
        for (t0, k0, v0), (t1, k1, v1) in zip(want, got):
            assert t0 == t1
            np.testing.assert_array_equal(k0, k1)
            np.testing.assert_array_equal(v0, v1)
    assert step._cache_size() == 1


@pytest.mark.parametrize("stack", ["grouped", "looped"])
def test_an_engine_on_the_list_serves_generates_tokens(stack, monkeypatch):
    """Through the engine, with chunks of 4 under tables of 15 slots: every
    request's greedy tokens are `generate`'s, whatever shared its batch and
    however many chunks a step walked; one compiled step."""
    monkeypatch.setattr(sched, "LIST_CHUNK", 4)
    cfg = STACKS[stack]
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    jobs = [(rng.integers(0, cfg.vocab_size, size=p).astype(np.int32), new)
            for p, new in ((3, 4), (8, 7), (11, 4), (5, 7), (8, 4), (20, 9))]
    engine = ServingEngine(params, cfg, page_size=8, max_batch=3,
                           num_pages=32, max_prompt_len=24, max_new_cap=16,
                           registry=obs.MetricRegistry())
    for prompt, new in jobs:
        engine.submit(prompt, new)
    results = engine.run()
    for rid, (prompt, new) in enumerate(jobs):
        want = gen.generate(params, jnp.asarray(prompt)[None], cfg, new)
        assert results[rid]["tokens"] == [
            int(t) for t in np.asarray(want)[0, len(prompt):]], rid
    assert engine.scheduler.decode_retraces() == 0
