"""Continuous-batching scheduler: prefill/decode split over paged KV.

The serving loop has exactly two compiled shapes:

- **prefill** — one full-sequence flash-attention pass per admitted
  request, bucketed to page-size multiples of prompt length (one jit
  per bucket, named ``_serving_prefill_s<S>`` so the recompile listener
  attributes them separately from the decode step);
- **decode** — ONE static-shape jit step (``_decode_step``) over the
  packed ``[max_batch]`` slot arrays and the donated page buffers. The
  batch composition (which requests occupy which slots, who is active)
  is data — block tables, positions and an active mask — never shape,
  so steady-state decode retraces exactly zero times. The page buffers
  ride the layer scan's carry and are written in place: the program
  holds the cache once and moves only the rows it writes and the pages
  that hold a position some active row attends to (a list made once a
  step, walked in chunks by a loop whose trip count follows it).

The engine keeps exactly one decode step in flight: an iteration
dispatches step ``k`` from what the host already knows (positions, tables
and the active mask are host mirrors; a row's next token is the output of
step ``k-1``, read where it lies on the device) and only then lands step
``k-1``'s array on the host, appends its tokens and retires what it
finished. The device has its next program queued when the current one ends;
the host's fetch, book-keeping, admission polling and uploads run beside it.

Every decode op is per-slot independent (row-wise gemms, per-row
attention over the row's own block table, per-row argmax), which is
what makes a request's token stream bit-identical regardless of what
else shares the batch — the property the preempt/resume chaos test
pins down. The list of live pages keeps it: a page's softmax is made
of the page and its row's query alone, and a row's pages are combined
in the order of the row's own columns, wherever the list held them.

A looped stack (``cfg.num_passes > 1``) makes both shapes' layer scan
``cfg.cache_layers`` steps long (``llama.scan_passes``): step ``i`` uses
the weights of layer ``i % L`` and cache layer ``i`` of the ``[T*L, ...]``
page buffers (prefill returns that layer's K and V, decode reads and
writes it where it lies). The programs, the cache and the dump all count
cache layers.

A stack of more than one layer kind goes through the same two shapes. A
dropless expert model (``cfg.dropless``: a dense lead, then expert layers
that route over all experts and compute those held here) is two scans,
one a stack (``llama.stacks``), each step ``llama.routed_block``; both
programs hand back, in the array the host already fetches, how many
assignments fell on held experts and how many held experts were touched.
Where some layers are sliding ones the decode step makes a second list of
live pages, bounded below by each row's window, and a layer walks the list
of its kind; the kind is data of the scan step. Pages below every sliding
layer's window stay allocated: one table and one pool for all layers.

Layers that are gated short convolutions (``cfg.hybrid``: ``llama.
short_conv``) go through the same two shapes and own no page. Prefill hands
back each conv layer's state at the prompt's true length beside K and V, the
seating writes it to the row's batch slot of the cache's state buffer, and
the decode step carries that buffer beside the page buffers, donated too: a
conv layer's scan step reads its layer's ``[K - 1, max_batch, hidden]`` of
it, puts the step's position behind it and writes the newest ``K - 1`` back
where they lay (an inactive row's stay as they were). The stack is scanned
run by run (``llama.stacks``: runs of one weight shape), K and V and the
states indexed by their own operator's layers.

Admission is FCFS: a request enters when a slot is free AND its whole
page worst case (padded prompt + max_new_tokens) can be allocated, so
an admitted request can never deadlock on pages mid-decode. Eviction
(EOS or length cap) frees pages and refills from the queue. A row that
ends by length is known by count and leaves the batch with its last
dispatch; one that ends by ``eos_id`` is seen when its token lands, a step
late, and has by then run one step more, whose token is dropped.
"""

from __future__ import annotations

import collections
import dataclasses
import math
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.models import generate as _gen
from apex_tpu.models import llama as _llama
from apex_tpu.observability import get_tracer, host_span
from apex_tpu.serving.kv_cache import (
    DISPATCHES,
    PagedKVCache,
    count_dispatch,
    state_hbm_bytes,
)

__all__ = [
    "ContinuousBatchScheduler",
    "Request",
    "build_decode_step",
    "build_prefill",
    "fp8_weight_scales",
    "pages_per_request",
]

_E4M3_MAX = 448.0
WEIGHT_MODES = ("native", "bf16", "fp8")
# Entries of the step's list of live pages that one trip of the decode
# attention's loop gathers and attends over; smaller tables are one chunk.
# From shapes, not from a caller.
LIST_CHUNK = 64
_MASKED = -1e30     # finite where a softmax has -inf: a row of no page gives 0
# What a dropless expert model's programs append to the tokens they return,
# each summed over the expert layers: the span fields they are recorded as
EXPERT_COUNTS = ("expert_tokens", "experts_hit")


@dataclasses.dataclass
class Request:
    """One serving request and its lifecycle timestamps (monotonic
    seconds; ``arrival_s`` is the loadgen trace offset). ``admit_s`` is
    taken when admission starts, before anything is dispatched: queue
    wait is ``admit_s - submit_s``, prefill ``first_token_s - admit_s``.
    """

    rid: int
    prompt: np.ndarray
    max_new_tokens: int
    arrival_s: float = 0.0
    submit_s: Optional[float] = None
    admit_s: Optional[float] = None
    first_token_s: Optional[float] = None
    finish_s: Optional[float] = None
    state: str = "queued"                 # queued -> active -> done
    tokens: List[int] = dataclasses.field(default_factory=list)


def _ns(seconds: float) -> int:
    """``time.monotonic()`` seconds on the span ring's nanosecond clock."""
    return int(seconds * 1e9)


def pages_per_request(prompt_len: int, max_new_tokens: int,
                      page_size: int) -> int:
    """Worst-case pages one request holds: the padded prompt bucket
    plus every decode write. Allocated whole at admission so decode
    can never stall on pages."""
    bucket = max(1, math.ceil(prompt_len / page_size)) * page_size
    return math.ceil((bucket + max_new_tokens) / page_size)


def fp8_weight_scales(params) -> Dict[str, jax.Array]:
    """Static per-layer weight scales (E4M3 amax scaling) for every
    dense layer kernel, stacked ``[L]`` to go among the weights, as
    ``lp["scales"]``, in the layer scan's ``shared`` (``llama.scan_passes``).
    Serving weights are frozen, so one amax pass at engine build
    replaces the training path's delayed-scaling ring."""
    out = {}
    for name in ("wq", "wk", "wv", "wo", "wg", "wu", "wd"):
        w = params["layers"][name].astype(jnp.float32)
        amax = jnp.max(jnp.abs(w), axis=tuple(range(1, w.ndim)))
        out[name] = _E4M3_MAX / jnp.maximum(amax, 1e-12)
    return out


def _normalize_weight_mode(weight_mode: str) -> str:
    if weight_mode not in WEIGHT_MODES:
        raise ValueError(f"weight_mode must be one of {WEIGHT_MODES}, "
                         f"got {weight_mode!r}")
    return "fp8" if weight_mode == "fp8" else "native"


def _products(weight_mode: str):
    """``llama.block``'s product hook for a weight mode: every layer gemm
    goes through it. ``native`` is the block's own plain matmul in the
    activation dtype (the exact op generate.py uses, so tokens match the
    reference decoder); ``fp8`` routes through
    :func:`~apex_tpu.ops.precision.matmul_fp8` with the static weight
    scales of :func:`fp8_weight_scales` at ``lp["scales"]``."""
    if _normalize_weight_mode(weight_mode) == "native":
        return _llama.products
    from apex_tpu.ops.precision import matmul_fp8

    def products(x, lp, *names):
        return (matmul_fp8(x, lp[n], jnp.float32(1.0),
                           lp["scales"][n]).astype(x.dtype) for n in names)

    return products


def _chunks(pages_live, table_slots: int):
    """How the decode step walks a list of ``pages_live`` entries out of
    tables of ``table_slots``: the entries of one chunk, and the chunks that
    hold a live entry (an int, or traced where ``pages_live`` is). The
    step's loop and the count of what it reads both come from here."""
    chunk = min(LIST_CHUNK, table_slots)
    return chunk, -(-pages_live // chunk)


def pages_read(pages_live: int, table_slots: int) -> int:
    """Pages a decode step gathers in every cache layer when ``pages_live``
    of its tables' ``table_slots`` entries hold a position some active row
    attends to: the whole chunks of its list."""
    chunk, n_chunks = _chunks(pages_live, table_slots)
    return n_chunks * chunk


def _live_page_list(tables, pos, active, page_size: int, trash: int,
                    start=None):
    """The decode step's list of live pages, made once a step from what the
    step is given: the tables' entries that hold a position some active row
    attends to (row ``r``'s first ``pos[r] // page_size + 1``; with
    ``start``, the first position each row reads in a sliding layer, those
    from column ``start[r] // page_size`` on, and the ``keys`` of that
    first page cut at ``start[r]``), row-major,
    ahead of all others, padded to whole chunks and cut into them (``[K,
    C]``). Per entry of the list: its row, its slot in the tables (``row *
    width + column``; ``tables.size``, one past them, for an entry past the
    live ones: what is scattered there is dropped), its page (``trash`` past
    the live ones) and which of its page's positions its row attends to
    (``[K, C, page_size]``); and the number of chunks that hold a live
    entry, a traced scalar."""
    w = tables.shape[1]
    live = jnp.arange(w) < jnp.where(active, pos // page_size + 1,
                                     0)[:, None]
    if start is not None:
        live = live & (jnp.arange(w) >= (start // page_size)[:, None])
    live = live.reshape(-1)
    n_live = jnp.sum(live, dtype=jnp.int32)
    chunk, n_chunks = _chunks(n_live, tables.size)
    order = jnp.pad(jnp.argsort(~live, stable=True).astype(jnp.int32),
                    (0, -live.size % chunk))
    valid = jnp.arange(order.size) < n_live
    row = jnp.where(valid, order // w, 0)
    first = jnp.where(valid, order % w, 0) * page_size
    slot = jnp.where(valid, order, tables.size)
    page = jnp.where(valid, tables.reshape(-1)[order], trash)
    keys = valid[:, None] & (
        first[:, None] + jnp.arange(page_size) <= pos[row][:, None])
    if start is not None:
        keys = keys & (first[:, None] + jnp.arange(page_size)
                       >= start[row][:, None])
    return tuple(a.reshape(-1, chunk, *a.shape[1:])
                 for a in (row, slot, page, keys)) + (n_chunks,)


def _attend_live_pages(q, kp, vp, at, live_pages, table_slots: int):
    """Decode attention of ``q [b, 1, nq, d]`` over the list's pages, read
    at ``at + page`` out of ``kp`` and ``vp`` (``[pages, page_size, nkv,
    d]``), in a loop over the chunks that hold a live entry. A chunk's pages
    are gathered and each is attended over by its own row's query alone, in
    float32; the page's softmax (its max, its sum, its weighted values) goes
    to the page's slot in tables shaped like the step's. A row's slots are
    then combined once, in the order of its columns. So what a row gets is
    made of its query, its pages and its position, whatever rows are beside
    it and wherever the list holds its pages: the same bits in any batch. A
    row with no live page (not active) comes out as zeros."""
    row, slot, page, keys, n_chunks = live_pages
    b, _, nq, d = q.shape
    nkv = int(np.prod(kp.shape[2:])) // d
    qg = q.astype(jnp.float32).reshape(b, nkv, nq // nkv, d)

    def heads(pages):               # [C, page, nkv, d], however a page lies
        return pages.astype(jnp.float32).reshape(*pages.shape[:2], nkv, d)

    def chunk(c, parts):
        r, ok = row[c], keys[c]
        kc, vc = heads(kp[at + page[c]]), heads(vp[at + page[c]])
        s = jnp.einsum("ckrd,ctkd->ckrt", qg[r], kc) * (d ** -0.5)
        s = jnp.where(ok[:, None, None], s, _MASKED)
        m = jnp.max(s, -1)
        p = jnp.exp(s - m[..., None])   # a masked position: exp(-1e30 - m) = 0
        new = (m, jnp.sum(p, -1), jnp.einsum("ckrt,ctkd->ckrd", p, vc))
        return tuple(a.at[slot[c]].set(x, mode="drop")
                     for a, x in zip(parts, new))

    zeros = jnp.zeros((table_slots,) + qg.shape[1:3], jnp.float32)
    m, l, o = (a.reshape(b, -1, *a.shape[1:]) for a in jax.lax.fori_loop(
        0, n_chunks, chunk,
        (jnp.full_like(zeros, _MASKED), zeros, jnp.zeros(zeros.shape + (d,)))))
    # a slot no page was written to weighs exp(-1e30 - max) = 0 and holds 0
    weight = jnp.exp(m - jnp.max(m, axis=1, keepdims=True))
    o = jnp.sum(o * weight[..., None], axis=1)
    l = jnp.sum(l * weight, axis=1)
    return (o / jnp.maximum(l, 1e-30)[..., None]).reshape(b, 1, nq * d)


def _refuse_unserved(cfg, weight_mode: str = "native") -> None:
    """The models no serving program is built for, by name."""
    if cfg.moe and not cfg.dropless:
        raise NotImplementedError(
            "serving runs dropless expert layers (moe_capacity_factor "
            "None): the capacity-dropped GShard/Mixtral form is training's "
            "and is not served")
    if cfg.hybrid and not cfg.cache_layers:
        raise NotImplementedError(
            "a stack of conv layers alone: the page pool and both programs "
            "count on at least one layer of K and V")
    if cfg.hybrid and _normalize_weight_mode(weight_mode) == "fp8":
        raise NotImplementedError(
            "conv layers with fp8 weights: no static scales are made for "
            "a stack kept in runs, nor for a conv operator's projections")
    if cfg.dropless and _normalize_weight_mode(weight_mode) == "fp8":
        raise NotImplementedError(
            "fp8 weights for a dropless expert model: no static scales "
            "are made for stacked expert weights")


def build_decode_step(cfg, page_size: int, weight_mode: str = "native"):
    """The ONE jit-compiled decode step (jit + donation is the
    caller's: ``jax.jit(step, donate_argnums=(2, 3, 4))``).

    ``(params, scales, k_pages, v_pages, conv_state, tokens, tables, pos,
    active, fresh, first) -> (next_tokens, k_pages, v_pages, conv_state)``
    — one signature for every model, ``conv_state`` ``None`` in and out
    where the model has no conv layers (no parameter, carry or output of
    the traced program: such a model's program is what it was). All batch
    inputs are packed ``[max_batch]`` slot arrays; ``tables`` is
    ``[max_batch, max_pages]`` of page indices (trash-padded). Inactive
    slots write their k/v to the trash page and pass their token
    through, so the step is total over any batch composition: one
    program, whose only data-dependent control is the trip count of the
    attention's loop. Greedy (argmax) by design — the
    bit-reproducibility contract. For a dropless expert model
    ``next_tokens`` is two entries longer: the assignments that fell on
    held experts and the held experts touched, each summed over the
    expert layers (:data:`EXPERT_COUNTS`).

    ``tokens`` is the ``next_tokens`` of the step before, as it lies on
    the device (the step reads its first ``max_batch`` entries): the
    host need not have seen them to dispatch this step. What the host
    alone knows is merged here, in the one program: a row where
    ``fresh`` is set (admitted or restored since that step) takes its
    token from ``first``.

    A model with conv layers (``cfg.hybrid``) gives the cache's
    ``conv_state`` ``[conv layers, K - 1, max_batch, hidden]``, donated
    like the pages, and gets it back updated. It rides the scan's carry
    as the page buffers do: conv layer ``i``'s step reads ``conv_state[i]``,
    puts the step's position behind it and writes the newest ``K - 1`` of
    each active row back where they lay.

    Arguments 2 and 3 are the whole cache and come back updated. The
    layer scan carries them beside the residual stream and scans over
    the cache layers' indices: step ``i`` scatters the batch's new rows
    into cache layer ``i`` and gathers pages from it, in the carried
    buffer itself. Donated, the outputs are the inputs' memory;
    nothing K- or V-shaped is the scan's ``xs`` or ``ys``, which would
    be a second copy of the cache written whole every step. The layer
    is ``llama.block``; what this step gives it is ``attend``, the
    scatter, the gather and the attention over what was gathered.

    What is gathered follows what is live. The step lists, once, the
    tables' entries that hold a position some active row attends to
    (:func:`_live_page_list`), and every layer walks that list in chunks
    of ``LIST_CHUNK`` pages (:func:`_attend_live_pages`): its reads and
    its arithmetic are the live pages', whatever the tables could hold.
    """
    _refuse_unserved(cfg, weight_mode)
    mm = _products(weight_mode)

    def _decode_step(params, scales, k_pages, v_pages, conv_state, tokens,
                     tables, pos, active, fresh, first):
        tokens = jnp.where(fresh, first, tokens[:tables.shape[0]])
        x = _llama.embed(params, tokens[:, None], cfg, tp_axis=None)
        shape = k_pages.shape           # [CL, P + 1, page, nkv, d]
        stride, trash = shape[1], shape[1] - 1
        page_idx = jnp.take_along_axis(
            tables, (pos // page_size)[:, None], axis=1)[:, 0]
        page_idx = jnp.where(active, page_idx, trash)
        off = pos % page_size
        live_pages = _live_page_list(tables, pos, active, page_size, trash)
        experts = _llama.expert_stack(params) if cfg.dropless else None
        if cfg.windowed:
            # the sliding layers' list beside the full layers', made once;
            # a layer indexes the pair by its kind
            live_pages = jax.tree_util.tree_map(
                lambda *a: jnp.stack(a), live_pages, _live_page_list(
                    tables, pos, active, page_size, trash,
                    _llama.sliding_start(cfg, pos)))

        # the buffers ride the carry as [CL * (P + 1), page, nkv, d] (a
        # bitcast): step i touches rows i * (P + 1) + page of them, so no
        # layer's slab is ever cut out or put back as a value
        flat = (shape[0] * stride,) + shape[2:]

        def body(carry, lp, i):
            h, kp, vp, *states = carry
            at = i * stride
            rows = at + page_idx

            mine = live_pages
            if cfg.windowed:
                kind = lp["sliding"].astype(jnp.int32)
                mine = jax.tree_util.tree_map(lambda a: a[kind], live_pages)

            def attend(q, k, v):
                kp1, vp1 = (buf.at[rows, off].set(new[:, 0].astype(
                    buf.dtype).reshape(-1, *shape[3:]))
                    for buf, new in ((kp, k), (vp, v)))
                o = _attend_live_pages(q, kp1, vp1, at, mine, tables.size)
                return o.astype(q.dtype), (kp1, vp1, *states)

            if _llama.operator_of(lp) == "conv":
                # conv layer i's rows of the carried state: read, the step's
                # position put behind them, the newest written back in place
                def attend(u):
                    (buf,) = states                 # [layers, K - 1, b, h]
                    past = jnp.swapaxes(buf[i], 0, 1)
                    full, new = _llama.conv_window(cfg, state=past)(u)
                    new = jnp.where(active[:, None, None],
                                    new.astype(buf.dtype), past)
                    return full, (kp, vp,
                                  buf.at[i].set(jnp.swapaxes(new, 0, 1)))

            if cfg.dropless:
                h, kept, counts = _llama.routed_block(
                    h, lp, experts, cfg, pos[:, None], attend, mm,
                    active[:, None])
                return (h, *kept), counts
            h, kept = _llama.block(h, lp, cfg, pos[:, None], attend, mm)
            return (h, *kept), None

        per_layer = jnp.arange(shape[0])
        if cfg.hybrid:
            per_layer = {"attention": per_layer,
                         "conv": jnp.arange(cfg.conv_layers)}
        (x, k_pages, v_pages, *states), counts = _llama.scan_passes(
            (x, k_pages.reshape(flat), v_pages.reshape(flat),
             *(() if conv_state is None else (conv_state,))), params, cfg,
            body, _llama.stacks(params, cfg, scales=scales), per_layer)
        k_pages, v_pages = k_pages.reshape(shape), v_pages.reshape(shape)
        logits = _gen._logits(params, x, cfg)[:, 0]
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        nxt = jnp.where(active, nxt, tokens)
        if cfg.dropless:
            # the counts ride the array the host fetches anyway
            nxt = jnp.concatenate([nxt, _summed(counts)])
        return nxt, k_pages, v_pages, (states[0] if states else None)

    return _decode_step


def _summed(counts):
    """A program's per-layer expert counts ``[layers, 2]``, summed over the
    layers; a stack with conv layers gives them by operator, as a dict."""
    leaves = jax.tree_util.tree_leaves(counts)
    return jnp.sum(leaves[0] if len(leaves) == 1
                   else jnp.concatenate(leaves), axis=0)


def build_prefill(cfg, bucket_len: int, weight_mode: str = "native"):
    """Jit'd full-sequence prefill for ONE prompt padded to
    ``bucket_len``: ``(params, scales, prompt [1, S], true_len) ->
    (first_token [1], ks [L, S, nkv, d], vs [L, S, nkv, d])``, ``L``
    being ``cfg.cache_layers``.

    For a dropless expert model ``first_token`` is two entries longer,
    as the decode step's array is (:data:`EXPERT_COUNTS`). For a model with
    conv layers a fourth output follows ``vs``: each conv layer's state
    ``[conv layers, K - 1, hidden]`` at ``true_len``.

    Causal flash attention means the pad suffix never contaminates
    real positions; the pad k/v land in the request's pages but decode
    overwrites index ``p + t`` before ever unmasking it. A causal
    convolution's outputs are as safe; the state it hands on is not the
    bucket's end's: it is taken at ``true_len`` (``llama.conv_window``),
    zeros before position 0 for a prompt shorter than ``K - 1``. The jit is
    named per bucket so prefill compiles never count against the
    decode step's zero-retrace guard.
    """
    _refuse_unserved(cfg, weight_mode)
    mm = _products(weight_mode)

    def prefill(params, scales, prompt, true_len):
        b, s = prompt.shape
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        x = _llama.embed(params, prompt, cfg, tp_axis=None)
        experts = _llama.expert_stack(params) if cfg.dropless else None

        def layer(h, lp, _):
            attend = _gen._prefill_attend(lp, cfg, true_len)
            if cfg.dropless:        # the pad suffix routes nowhere
                h, kept, counts = _llama.routed_block(
                    h, lp, experts, cfg, positions, attend, mm,
                    positions < true_len)
                return h, (kept, counts)
            return _llama.block(h, lp, cfg, positions, attend, mm)

        x, kept = _llama.scan_passes(
            x, params, cfg, layer, _llama.stacks(params, cfg, scales=scales))
        # what the layers kept, by operator; a dropless layer's counts beside
        by_op = kept if cfg.hybrid else {"attention": kept}
        if cfg.dropless:
            counts = _summed({op: c for op, (_, c) in by_op.items()})
            by_op = {op: k for op, (k, _) in by_op.items()}
        ks, vs = by_op["attention"]
        x_last = jax.lax.dynamic_slice_in_dim(x, true_len - 1, 1,
                                              axis=1)
        logits = _gen._logits(params, x_last, cfg)[:, 0]
        first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if cfg.dropless:
            first = jnp.concatenate([first, counts])
        return (first, ks[:, 0].astype(cfg.dtype),
                vs[:, 0].astype(cfg.dtype)) + (
            (by_op["conv"][:, 0].astype(cfg.dtype),) if cfg.hybrid else ())

    prefill.__name__ = f"_serving_prefill_s{bucket_len}"
    prefill.__qualname__ = prefill.__name__
    return jax.jit(prefill)


class ContinuousBatchScheduler:
    """Queue + slots + paged cache behind the two compiled shapes.

    Host mirrors (numpy) of the positions, the tables and the active
    mask are the source of truth; each decode step re-wraps them as
    device arrays (same shapes every step — data changes, shapes never
    do). They say what the next dispatch runs: a position moves on when
    its step is dispatched, not when its token lands. The tokens are not
    mirrored: a row's next input is the newest step's output, kept on
    the device (``_newest``), and the host holds only the first token of
    each row admitted or restored since the last dispatch (``_first``
    under the mask ``_fresh``). One step's output at most is unlanded
    (``_unlanded``): :meth:`step_decode` dispatches, then lands the step
    before; :meth:`land` lands without dispatching.
    """

    def __init__(self, params, cfg, *, num_pages: int,
                 page_size: int = 8, max_batch: int = 4,
                 max_prompt_len: int = 64, max_new_cap: int = 32,
                 weight_mode: str = "native",
                 eos_id: Optional[int] = None):
        _refuse_unserved(cfg, weight_mode)
        if max_batch < 1 or page_size < 1:
            raise ValueError("max_batch and page_size must be >= 1")
        self.params = params
        self.cfg = cfg
        self.page_size = int(page_size)
        self.max_batch = int(max_batch)
        self.max_prompt_len = int(max_prompt_len)
        self.max_new_cap = int(max_new_cap)
        self.eos_id = eos_id
        self.weight_mode = _normalize_weight_mode(weight_mode)
        self.max_pages_per_req = pages_per_request(
            max_prompt_len, max_new_cap, page_size)
        if num_pages < self.max_pages_per_req:
            raise ValueError(
                f"num_pages={num_pages} cannot hold even one "
                f"worst-case request ({self.max_pages_per_req} pages "
                f"for prompt {max_prompt_len} + {max_new_cap} new)")
        self.cache = PagedKVCache(cfg, num_pages, page_size,
                                  max_batch=self.max_batch)
        # what the admit and decode records say of the model's depth: the
        # layers a token goes through (the stack's, times its passes), the
        # layers of K and V a page holds and, where there are any, the conv
        # layers, which hold state a row and no page
        self._depth = {"layer_passes": cfg.num_passes * cfg.num_layers,
                       "cache_layers": self.cache.layers}
        if cfg.hybrid:
            self._depth["conv_layers"] = cfg.conv_layers
        # one row's conv state over all conv layers, in bytes (0 without)
        self._row_state_bytes = state_hbm_bytes(cfg, 1, self.cache.dtype)
        if cfg.dropless:
            self._depth.update(expert_layers=cfg.expert_layers,
                               experts_held=cfg.held[1])
        self.queue: "collections.deque[Request]" = collections.deque()
        self.slots: List[Optional[Request]] = [None] * self.max_batch
        trash = self.cache.trash_page
        self._pos = np.zeros(self.max_batch, np.int32)
        self._tables = np.full(
            (self.max_batch, self.max_pages_per_req), trash, np.int32)
        self._active = np.zeros(self.max_batch, bool)
        self._first = np.zeros(self.max_batch, np.int32)
        self._fresh = np.zeros(self.max_batch, bool)
        # the newest step's output, on the device: the next step's tokens
        self._newest = jnp.zeros(
            self.max_batch + len(EXPERT_COUNTS) * bool(cfg.dropless),
            jnp.int32)
        # the one step whose output the host has not read: its array and
        # the (slot, request) pairs of the rows it ran
        self._unlanded: Optional[Tuple[jax.Array, list]] = None
        self._scales = (fp8_weight_scales(params)
                        if self.weight_mode == "fp8" else {})
        self._decode = jax.jit(
            build_decode_step(cfg, self.page_size, self.weight_mode),
            donate_argnums=(2, 3, 4))
        self._prefills: Dict[int, object] = {}
        self.decode_steps = 0
        self.prefill_count = 0
        # steps dispatched while the one before was unlanded; rows that ran
        # one step past their EOS (their extra token dropped)
        self.steps_in_flight = 0
        self.rows_past_eos = 0
        # installed before anything compiles, so the guard below can tell
        # "no retrace" from "the listener never saw a compile"
        from apex_tpu.observability import recompile
        self._recompiles = recompile.install()
        # compile count of "_decode_step" right after OUR first compile
        # — the zero-retrace guard's baseline (delta, so other engines'
        # earlier compiles of the same-named step don't count here)
        self._decode_compiles0: Optional[int] = None

    # --------------------------------------------------------- queries

    def occupancy(self) -> float:
        return float(np.count_nonzero(self._active)) / self.max_batch

    def has_work(self) -> bool:
        """Queued or running requests, or a step's tokens to land."""
        return bool(self.queue) or self._unlanded is not None or any(
            r is not None for r in self.slots)

    def num_active(self) -> int:
        return int(np.count_nonzero(self._active))

    def decode_retraces(self) -> int:
        """Recompiles of ``_decode_step`` after this scheduler's own
        first compile — steady-state must report 0."""
        if self._decode_compiles0 is None:
            return 0
        return max(0, self._recompiles.compiles("_decode_step")
                   - self._decode_compiles0)

    # ------------------------------------------------------- admission

    def submit(self, req: Request) -> None:
        p = len(req.prompt)
        if not 1 <= p <= self.max_prompt_len:
            raise ValueError(f"prompt length {p} outside "
                             f"[1, {self.max_prompt_len}]")
        if not 1 <= req.max_new_tokens <= self.max_new_cap:
            raise ValueError(
                f"max_new_tokens {req.max_new_tokens} outside "
                f"[1, {self.max_new_cap}]")
        self.queue.append(req)

    def pages_needed(self, req: Request) -> int:
        return pages_per_request(len(req.prompt), req.max_new_tokens,
                                 self.page_size)

    def try_admit(self) -> Tuple[List[Request], List[Request]]:
        """Admit FCFS while a slot is free and the head request's
        worst-case pages fit; returns ``(admitted, finished)`` —
        finished covers single-token (or instant-EOS) requests that
        complete inside their own prefill."""
        admitted, finished = [], []
        while self.queue and None in self.slots:
            if not self.cache.alloc.can_alloc(
                    self.pages_needed(self.queue[0])):
                break
            req = self.queue.popleft()
            admitted.append(req)
            if not self._admit(req):
                finished.append(req)
        return admitted, finished

    def _bucket(self, p: int) -> int:
        return max(1, math.ceil(p / self.page_size)) * self.page_size

    def _prefill_for(self, bucket_len: int):
        fn = self._prefills.get(bucket_len)
        if fn is None:
            fn = build_prefill(self.cfg, bucket_len, self.weight_mode)
            self._prefills[bucket_len] = fn
        return fn

    def _admit(self, req: Request) -> bool:
        """Prefill + slot placement; returns False when the request
        finished at its first token (no slot taken)."""
        req.admit_s = time.monotonic()
        p = len(req.prompt)
        s_pad = self._bucket(p)
        # rows: the decoding rows that get no token while this runs
        with host_span("serving/admit", rid=req.rid, prompt_tokens=p,
                       bucket=s_pad, rows=self.num_active(), **self._depth):
            if req.submit_s is not None:
                get_tracer().record("serving/queue_wait",
                                    _ns(req.submit_s), _ns(req.admit_s),
                                    rid=req.rid)
            with host_span("serving/prefill_dispatch", rid=req.rid,
                           bucket=s_pad, program_seq=count_dispatch(
                               f"_serving_prefill_s{s_pad}")):
                pages = self.cache.alloc.alloc(self.pages_needed(req),
                                               req.rid)
                prompt = np.zeros((1, s_pad), np.int32)
                prompt[0, :p] = req.prompt
                first, ks, vs, *state = self._prefill_for(s_pad)(
                    self.params, self._scales, jnp.asarray(prompt),
                    np.int32(p))
            self.prefill_count += 1
            n_prompt = s_pad // self.page_size
            # the cache counts its two page writes, K's and then V's
            with host_span("serving/write_prompt", rid=req.rid,
                           pages=n_prompt, cache_layers=self.cache.layers,
                           program_seq=DISPATCHES["_serving_write_pages"]):
                self.cache.write_prompt(pages[:n_prompt], ks, vs)
            with host_span("serving/first_token_fetch", rid=req.rid):
                first = np.asarray(first)
                t0 = int(first[0])
            if self.cfg.dropless:
                get_tracer().annotate(expert_tokens=int(first[1]))
            req.tokens = [t0]
            req.first_token_s = time.monotonic()
            if self._is_finished(req, t0):
                self._retire(req)
                return False
            self._seat(req, pages, p, *state)
            return True

    def _seat(self, req: Request, pages, pos: int, state=None) -> None:
        """Give ``req`` a free slot: its next step reads its newest token
        from the host and writes position ``pos`` of ``pages``; ``state``,
        its conv layers' at ``pos``, goes into the slot whole, over whatever
        the slot's last row left."""
        slot = self.slots.index(None)
        if state is not None:
            with host_span("serving/write_state", rid=req.rid,
                           conv_layers=self.cfg.conv_layers,
                           bytes=self._row_state_bytes,
                           program_seq=DISPATCHES["_serving_write_state"]):
                self.cache.write_state(slot, state)
        self.slots[slot] = req
        req.state = "active"
        self._first[slot] = req.tokens[-1]
        self._fresh[slot] = True
        self._pos[slot] = pos
        row = np.full(self.max_pages_per_req, self.cache.trash_page,
                      np.int32)
        row[:len(pages)] = pages
        self._tables[slot] = row
        self._active[slot] = True

    # ---------------------------------------------------------- decode

    def pages_live(self, window: Optional[int] = None) -> int:
        """Pages the next decode step reads that hold a position some
        active row attends to: each row's ``ceil((pos + 1) / page_size)``,
        from the host mirrors; with ``window``, those a sliding layer
        reads: from the page of ``pos - window + 1`` on."""
        pos = self._pos[self._active]
        start = 0 if window is None else np.maximum(pos - window + 1, 0)
        return int(np.sum(pos // self.page_size
                          - start // self.page_size + 1))

    def step_decode(self) -> List[Request]:
        """Dispatch one packed decode step, then land the step before:
        append its tokens and return the requests they finished. The
        tokens a call appends are those of the step the call before
        dispatched; with nothing unlanded it appends none. With no row to
        run it lands what is unlanded (:meth:`land`).

        In the span ring a dispatched step is one ``serving/decode``:
        ``rows`` and the page counts are the dispatched step's,
        ``in_flight`` is 1 when the step before was still unlanded at the
        dispatch (the device had its next program queued), and what only
        the landed array can say is **of the array landed during the
        record**, the step before's (zeros when nothing was unlanded):
        ``rows_past_eos``, the rows of the dispatched step that the landed
        tokens show to be past their EOS, and a dropless expert model's
        :data:`EXPERT_COUNTS`. Its ``serving/decode_upload`` carries
        ``program_seq``, the step's ordinal among the process's dispatches
        of ``_decode_step``, as the prefill's, the page writes' and the
        state write's spans carry theirs."""
        if not self._active.any():
            return self.land()
        # pages_gathered: what the step reads in every cache layer
        live = self.pages_live()
        fields = dict(self._depth)
        if self.cfg.windowed:
            # what the sliding layers read, beside what the full ones do
            near = self.pages_live(self.cfg.sliding_window)
            seen = self._pos[self._active] + 1
            fields.update(
                pages_live_window=near,
                pages_gathered_window=pages_read(near, self._tables.size),
                positions=int(seen.sum()), positions_window=int(
                    np.minimum(seen, self.cfg.sliding_window).sum()))
        if self.cfg.hybrid:
            # the conv state the step reads and rewrites: its active rows';
            # the positions its attention layers read
            fields.update(
                state_bytes=self._row_state_bytes * self.num_active(),
                positions=int((self._pos[self._active] + 1).sum()))
        in_flight = int(self._unlanded is not None)
        self.steps_in_flight += in_flight
        with host_span("serving/decode", rows=self.num_active(),
                       pages_live=live,
                       pages_gathered=pages_read(live, self._tables.size),
                       in_flight=in_flight, **fields):
            with host_span("serving/decode_upload",
                           program_seq=count_dispatch("_decode_step")):
                # copies: the mirrors change while the step is queued, and
                # a transfer may read its host array after the call returns
                mirrors = (jnp.asarray(a.copy()) for a in (
                    self._tables, self._pos, self._active, self._fresh,
                    self._first))
                cache = self.cache
                (self._newest, cache.k_pages, cache.v_pages,
                 cache.conv_state) = self._decode(
                    self.params, self._scales, cache.k_pages, cache.v_pages,
                    cache.conv_state, self._newest, *mirrors)
            self.decode_steps += 1
            if self._decode_compiles0 is None:
                self._decode_compiles0 = self._recompiles.compiles(
                    "_decode_step")
                if self._decode_compiles0 < 1:
                    raise RuntimeError(
                        "the recompile listener did not see _decode_step "
                        "compile: the zero-retrace guard is blind")
            dispatched = (self._newest, self._advance())
            finished, said = self._land(ran_on=True)
            self._unlanded = dispatched
            get_tracer().annotate(**said)
            return finished

    def _advance(self) -> List[Tuple[int, Request]]:
        """The host mirrors after a dispatch: the (slot, request) pairs of
        the rows it ran, each a position on; no row is fresh; a row whose
        token in flight is its last by count runs no more (its slot is
        held until that token lands)."""
        rows = [(int(slot), self.slots[slot])
                for slot in np.flatnonzero(self._active)]
        self._pos[self._active] += 1
        self._fresh[:] = False
        for slot, req in rows:
            # made, landed or in flight: the first with the prompt, then one
            # a position
            if self._pos[slot] - len(req.prompt) + 1 >= req.max_new_tokens:
                self._active[slot] = False
        return rows

    def land(self) -> List[Request]:
        """Read the unlanded step's tokens without dispatching another:
        what ends a run of steps (no row left to run) and what a dump
        does first. Returns the requests the tokens finished."""
        return self._land()[0]

    def _land(self, ran_on: bool = False
              ) -> Tuple[List[Request], Dict[str, int]]:
        """The unlanded step's array onto the host: (the requests its
        tokens finished, what the array says for the span record:
        ``rows_past_eos`` and a dropless expert model's counts of that
        step; zeros with nothing unlanded). A row that an earlier landing
        retired (its EOS came a step before) ran this step for nothing:
        its token is dropped. ``ran_on``: a step has been dispatched
        since, with every row not yet at its length in it."""
        said = dict.fromkeys(
            ("rows_past_eos",) + EXPERT_COUNTS * bool(self.cfg.dropless), 0)
        if self._unlanded is None:
            return [], said
        (nxt, rows), self._unlanded = self._unlanded, None
        with host_span("serving/decode_fetch"):
            nxt = np.asarray(nxt)
        said.update(zip(EXPERT_COUNTS, map(int, nxt[self.max_batch:])))
        finished = []
        with host_span("serving/decode_bookkeep"):
            for slot, req in rows:
                if req.state == "done":
                    continue
                t = int(nxt[slot])
                req.tokens.append(t)
                if self._is_finished(req, t):
                    said["rows_past_eos"] += int(
                        ran_on and len(req.tokens) < req.max_new_tokens)
                    self._free_slot(slot)
                    self._retire(req)
                    finished.append(req)
        self.rows_past_eos += said["rows_past_eos"]
        return finished, said

    def _is_finished(self, req: Request, token: int) -> bool:
        return (len(req.tokens) >= req.max_new_tokens
                or (self.eos_id is not None and token == self.eos_id))

    def _retire(self, req: Request) -> None:
        req.state = "done"
        req.finish_s = time.monotonic()
        if req.submit_s is not None:
            get_tracer().record("serving/request", _ns(req.submit_s),
                                _ns(req.finish_s), rid=req.rid,
                                tokens=len(req.tokens))
        self.cache.alloc.free_owner(req.rid)

    def _free_slot(self, slot: int) -> None:
        self.slots[slot] = None
        self._active[slot] = False
        self._tables[slot] = self.cache.trash_page
        self._pos[slot] = 0

    # --------------------------------------------------- dump / resume

    def _req_record(self, req: Request) -> dict:
        return {"rid": req.rid,
                "prompt": [int(t) for t in req.prompt],
                "max_new_tokens": int(req.max_new_tokens),
                "arrival_s": float(req.arrival_s)}

    def export_requests(self):
        """Emergency-dump payload: (queued records, inflight records,
        {name: numpy} page arrays). Inflight k/v pages are gathered so
        resume restores them by scatter — re-prefilling would re-run
        float math and forfeit bit-identical resumption; a row's conv
        state (``s_<rid>``, where the model has conv layers) goes beside
        them, as it lies in the row's slot. Every token made
        has to be on the host: the caller lands the step in flight first
        (:meth:`land`), so a record's ``tokens`` and ``pos`` agree."""
        if self._unlanded is not None:
            raise RuntimeError(
                "a decode step's tokens are unlanded: land() first, and "
                "keep what it finished")
        queued = [self._req_record(r) for r in self.queue]
        inflight, arrays = [], {}
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            pages = self.cache.alloc.pages_of(req.rid)
            k, v = self.cache.gather_pages(pages)
            arrays[f"k_{req.rid}"] = k
            arrays[f"v_{req.rid}"] = v
            if self.cfg.hybrid:
                arrays[f"s_{req.rid}"] = self.cache.gather_state(slot)
            rec = self._req_record(req)
            rec.update(pos=int(self._pos[slot]),
                       tokens=[int(t) for t in req.tokens],
                       npages=len(pages))
            inflight.append(rec)
        return queued, inflight, arrays

    def import_request(self, rec: dict, k, v, state=None) -> Request:
        """Rebuild one in-flight request from a dump record + its
        gathered pages and, where the model has conv layers, its conv
        state (resume path)."""
        if (state is None) == self.cfg.hybrid:
            raise ValueError("a dumped request has conv state if, and only "
                             "if, the model has conv layers")
        req = Request(rid=rec["rid"],
                      prompt=np.asarray(rec["prompt"], np.int32),
                      max_new_tokens=rec["max_new_tokens"],
                      arrival_s=rec.get("arrival_s", 0.0),
                      submit_s=time.monotonic())
        req.admit_s = req.submit_s
        pages = self.cache.alloc.alloc(rec["npages"], req.rid)
        self.cache.restore_pages(pages, k, v)
        req.tokens = list(rec["tokens"])
        req.first_token_s = time.monotonic()
        self._seat(req, pages, rec["pos"], state)
        return req
