"""Paged KV cache for the continuous-batching serving runtime.

The cache is two donated device buffers ``[L, P + 1, page_size, nkv, d]``
(k and v) plus a host-side free-list allocator with per-request page
accounting. ``L`` is ``cfg.cache_layers``, the layers of K and V, which a
looped stack has ``num_passes`` times as many of as layers of weights
(pass-major: pass ``t`` of layer ``l`` is cache layer ``t * num_layers +
l``); a page, the budget and every transfer are sized by it. Requests
own page lists; the scheduler maps them into a static ``[B, max_pages]``
block table consumed by the jit decode step, so the device side never
sees a dynamic shape.

Layers that are gated short convolutions (``cfg.conv_layers`` of them) own
no page: such a layer keeps ``[conv_L_cache - 1, hidden]`` a row, whatever
the context. Their states lie beside the pages in one buffer ``[conv layers,
conv_L_cache - 1, max_batch, hidden]`` (:attr:`PagedKVCache.conv_state`; the
rows second to last, as the device tiles them: with the positions there the
decode step converted the buffer on its way in and out), addressed by batch
slot: written whole when a row is seated, rewritten by
every decode step, dead when the row leaves (the next row's seating
overwrites it), and carried by a dump beside the row's pages. ``defrag`` moves
pages, not slots, and leaves it alone.

Page ``P`` (the last one) is the *trash page*: inactive batch slots
scatter their (masked, never-read) k/v writes there, which keeps the
decode step total — no ``lax.cond`` per slot, no out-of-bounds scatter.
The allocator never hands it out.

The page *budget* is derived from the live memory tier rather than
guessed: usable HBM = ``device_hbm_bytes()`` × safety − what the device
already holds (the ``MemoryMonitor`` watermark, else the allocator's
``bytes_in_use`` — the weights), divided by the per-page footprint. The
``hbm_priors.json`` measured/modeled ratio (PR 18) corrects the
footprint only where it was measured on the backend in use: a ratio
below 1 taken on another backend would add pages that do not fit. On a
TPU v5 lite the page buffers occupy their modeled bytes (ratio 1.0003
measured for ``[8, 1153, 8, 8, 128]`` bf16 — the (8, 128) minor dims are
not padded).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import math
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "PageAllocator",
    "PageBudget",
    "PagedKVCache",
    "derive_page_budget",
    "page_dims",
    "page_hbm_bytes",
    "state_hbm_bytes",
]


def page_hbm_bytes(cfg, page_size: int, dtype=None) -> int:
    """Modeled HBM bytes of ONE page: k + v across all cache layers."""
    dtype = cfg.dtype if dtype is None else dtype
    itemsize = jnp.dtype(dtype).itemsize
    return (2 * cfg.cache_layers * page_size * cfg.num_kv_heads
            * cfg.head_dim * itemsize)


def page_dims(cfg):
    """The dims of one position of a page: ``(nkv, d)``; the heads side by
    side, ``(nkv * d,)``, where one head is narrower than the 128 lanes of
    the device's tiles and a position's heads together fill whole tiles. A
    ``[..., 8, 64]`` buffer is padded to twice its bytes in the device's
    default layout, and a decode step that prefers another converts the
    whole pool on its way in and out (1.6 GB of copies a step at LFM2's
    widths); ``[..., 512]`` is neither. The decode step and the transfers
    follow the buffer's shape; K and V of a position are the same numbers in
    the same order either way."""
    nkv, d = cfg.num_kv_heads, cfg.head_dim
    return (nkv * d,) if d % 128 and not (nkv * d) % 128 else (nkv, d)


def state_hbm_bytes(cfg, rows: int, dtype=None) -> int:
    """Modeled HBM bytes of the conv layers' state of ``rows`` batch rows:
    ``conv_L_cache - 1`` positions of ``hidden`` a layer and row; 0 for a
    model without conv layers."""
    dtype = cfg.dtype if dtype is None else dtype
    return (cfg.conv_layers * rows * (cfg.conv_L_cache - 1)
            * cfg.hidden_size * jnp.dtype(dtype).itemsize)


@dataclasses.dataclass(frozen=True)
class PageBudget:
    """The derivation trail of a page budget (kept for telemetry/docs —
    a budget that can't explain itself can't be debugged)."""

    pages: int
    page_bytes: int          # modeled bytes per page
    ratio: float             # measured/modeled correction (1.0 = none)
    hbm_bytes: int           # device HBM limit used
    watermark_bytes: int     # bytes the device already holds, subtracted
    usable_bytes: int        # hbm * safety - watermark - state (floored at 0)
    safety: float
    state_bytes: int = 0     # the conv layers' state buffer, subtracted


def derive_page_budget(cfg, page_size: int, *,
                       hbm_bytes: Optional[int] = None,
                       watermark_bytes: Optional[int] = None,
                       priors: Optional[dict] = None,
                       safety: float = 0.90,
                       dtype=None, state_rows: int = 0) -> PageBudget:
    """Page budget from the live memory tier.

    ``pages = floor((hbm × safety − watermark − state) / (page_bytes ×
    ratio))``, ``state`` being the conv layers' state of ``state_rows`` batch
    rows (:func:`state_hbm_bytes`), which lies beside the pages.
    Every input is overridable for tests; defaults read the live tier:
    ``device_hbm_bytes()``; the active ``MemoryMonitor`` watermark, or
    with no monitor attached the allocator's ``bytes_in_use`` (0 where
    the backend reports none, i.e. the CPU); and the committed
    ``hbm_priors.json`` — whose ratio (the serving prior, else its
    default) is applied only when it was measured on the backend in
    use, and is 1.0 otherwise.
    """
    from apex_tpu.analysis.memory_checks import load_hbm_priors, prior_for
    from apex_tpu.observability.memory import hbm
    from apex_tpu.ops.pallas_config import device_hbm_bytes

    if not 0.0 < safety <= 1.0:
        raise ValueError(f"safety must be in (0, 1], got {safety}")
    if hbm_bytes is None:
        hbm_bytes = device_hbm_bytes()
    if watermark_bytes is None:
        mon = hbm.active_monitor()
        watermark_bytes = (
            mon.watermark_bytes if mon is not None
            else hbm.device_memory_stats().get("bytes_in_use", 0))
    if priors is None:
        priors = load_hbm_priors()
    ratio = 1.0
    if priors.get("backend") == jax.default_backend():
        ratio = prior_for("serving_decode_step", priors, default=True)
    page_bytes = page_hbm_bytes(cfg, page_size, dtype=dtype)
    state_bytes = state_hbm_bytes(cfg, state_rows, dtype=dtype)
    usable = max(0, int(hbm_bytes * safety) - int(watermark_bytes)
                 - state_bytes)
    pages = int(usable // max(1, int(math.ceil(page_bytes * ratio))))
    return PageBudget(pages=pages, page_bytes=page_bytes, ratio=ratio,
                      hbm_bytes=int(hbm_bytes),
                      watermark_bytes=int(watermark_bytes),
                      usable_bytes=usable, safety=safety,
                      state_bytes=state_bytes)


class PageAllocator:
    """Free-list page allocator with per-owner accounting.

    Pages are plain ints in ``[0, num_pages)``; owners are request ids.
    Allocation is all-or-nothing (the admission check), frees are by
    owner (eviction returns every page a request held).
    """

    def __init__(self, num_pages: int):
        if num_pages < 1:
            raise ValueError(f"need at least 1 page, got {num_pages}")
        self.num_pages = num_pages
        self._free: List[int] = list(range(num_pages - 1, -1, -1))
        self._owned: Dict[object, List[int]] = {}

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_used(self) -> int:
        return self.num_pages - len(self._free)

    def owners(self):
        return list(self._owned)

    def pages_of(self, owner) -> List[int]:
        return list(self._owned.get(owner, ()))

    def can_alloc(self, n: int) -> bool:
        return 0 < n <= len(self._free)

    def alloc(self, n: int, owner) -> List[int]:
        if n < 1:
            raise ValueError(f"alloc needs n >= 1, got {n}")
        if n > len(self._free):
            raise RuntimeError(
                f"out of KV pages: want {n}, have {len(self._free)} "
                f"free of {self.num_pages} (admission must check "
                f"can_alloc first)")
        pages = [self._free.pop() for _ in range(n)]
        self._owned.setdefault(owner, []).extend(pages)
        return pages

    def free_owner(self, owner) -> int:
        """Return every page held by ``owner``; returns the count."""
        pages = self._owned.pop(owner, [])
        # freed pages go back lowest-first so reuse stays compact
        self._free.extend(pages)
        self._free.sort(reverse=True)
        return len(pages)

    def live_pages(self) -> List[int]:
        return sorted(p for pages in self._owned.values() for p in pages)


# Each serving program's dispatches so far in this process, by the name its
# module carries in a device trace: a span that dispatches one records the
# count before its call as ``program_seq``, so a reader pairs the k-th such
# program of a trace with the k-th dispatch by order, not by nearest time
DISPATCHES: "collections.Counter[str]" = collections.Counter()


def count_dispatch(program: str, n: int = 1) -> int:
    """Count ``n`` dispatches of ``program`` (:data:`DISPATCHES`) and return
    the ordinal of the first: the ``program_seq`` of the span queuing it."""
    seq = DISPATCHES[program]
    DISPATCHES[program] = seq + n
    return seq


@functools.partial(jax.jit, donate_argnums=(0,))
def _serving_write_pages(buf, idx, payload):
    """``buf[:, idx] = payload`` on the donated buffer, so in place: the
    un-jitted ``.at[:, idx].set`` allocated a second whole buffer for as
    long as the write ran, which does not fit once the cache is most of
    the chip (a looped stack's is). ``payload`` is
    ``[L, n * page_size, nkv, d]`` (prefill's) or ``[L, n, page_size, nkv,
    d]`` (a dump's), laid out as the buffer's pages are (:func:`page_dims`).
    One compile per number of pages, named apart from the
    decode step for the recompile listener."""
    pages = payload.astype(buf.dtype).reshape(
        buf.shape[0], idx.shape[0], *buf.shape[2:])
    return buf.at[:, idx].set(pages)


@functools.partial(jax.jit, donate_argnums=(0,))
def _serving_write_state(buf, slot, state):
    """``buf[:, :, slot] = state`` on the donated state buffer, in place: a
    row's conv state ``[conv layers, conv_L_cache - 1, hidden]`` into its
    batch slot. One compile, whatever the slot."""
    return buf.at[:, :, slot].set(state.astype(buf.dtype))


class PagedKVCache:
    """The device-side paged cache + its allocator.

    Buffers are ``[L, P + 1, page_size, nkv, d]`` in ``cfg.dtype`` (``[L, P
    + 1, page_size, nkv * d]`` where heads are narrow: :func:`page_dims`),
    ``L`` being :attr:`layers` (``cfg.cache_layers``); the
    extra page at index ``P`` (:attr:`trash_page`) absorbs inactive-slot
    scatter writes. The scheduler donates both buffers into the decode
    jit each step and stores the outputs back here. A model with conv
    layers has :attr:`conv_state` beside them, ``[cfg.conv_layers,
    conv_L_cache - 1, max_batch, hidden]``, donated and stored back alike
    (None for a model without).
    """

    def __init__(self, cfg, num_pages: int, page_size: int, dtype=None,
                 max_batch: int = 0):
        self.cfg = cfg
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.dtype = cfg.dtype if dtype is None else dtype
        self.alloc = PageAllocator(self.num_pages)
        self.layers = int(cfg.cache_layers)
        shape = (self.layers, self.num_pages + 1, self.page_size,
                 *page_dims(cfg))
        self.k_pages = jnp.zeros(shape, self.dtype)
        self.v_pages = jnp.zeros(shape, self.dtype)
        self.conv_state = None
        if cfg.conv_layers:
            if max_batch < 1:
                raise ValueError("a model with conv layers keeps state a "
                                 "batch row: the cache needs max_batch")
            self.conv_state = jnp.zeros(
                (cfg.conv_layers, cfg.conv_L_cache - 1, int(max_batch),
                 cfg.hidden_size), self.dtype)

    @property
    def trash_page(self) -> int:
        return self.num_pages

    def utilization(self) -> float:
        return self.alloc.num_used / self.num_pages

    def page_pool_bytes(self) -> int:
        return 2 * int(np.prod(self.k_pages.shape)) * jnp.dtype(
            self.dtype).itemsize

    def state_bytes(self) -> int:
        """Bytes of the conv layers' state buffer (0 without one)."""
        return 0 if self.conv_state is None else int(
            self.conv_state.size) * jnp.dtype(self.dtype).itemsize

    def hbm_bytes(self) -> int:
        return self.page_pool_bytes() + self.state_bytes()

    # --------------------------------------------------------- transfers

    def write_prompt(self, pages: List[int], ks, vs) -> None:
        """Store prefill k/v ``[L, S, nkv, d]`` (S = len(pages) × page
        size) into ``pages`` in order."""
        L = self.layers
        n = len(pages)
        s = ks.shape[1]
        if s != n * self.page_size:
            raise ValueError(f"prefill length {s} != {n} pages × "
                             f"{self.page_size}")
        if ks.shape[0] != L or vs.shape != ks.shape:
            raise ValueError(f"prefill k/v {ks.shape} / {vs.shape} do not "
                             f"hold this cache's {L} layers")
        idx = jnp.asarray(pages, jnp.int32)
        self.k_pages = _serving_write_pages(self.k_pages, idx, ks)
        self.v_pages = _serving_write_pages(self.v_pages, idx, vs)
        count_dispatch("_serving_write_pages", 2)

    def gather_pages(self, pages: List[int]):
        """Fetch ``pages`` to host as ``(k, v)`` numpy arrays
        ``[L, n, page_size, nkv, d]`` — the emergency-dump payload."""
        idx = jnp.asarray(pages, jnp.int32)
        return (np.asarray(self.k_pages[:, idx]),
                np.asarray(self.v_pages[:, idx]))

    def restore_pages(self, pages: List[int], k, v) -> None:
        """Scatter a dumped payload back (resume path). Restoring by
        scatter — not re-prefilling — is what keeps resumed decodes
        bit-identical to the uninterrupted run."""
        want = (self.layers, len(pages)) + self.k_pages.shape[2:]
        if k.shape != want or v.shape != want:
            raise ValueError(
                f"dumped pages {k.shape} / {v.shape} do not fit this cache "
                f"({want}: cache layers, pages, page size, kv heads, d)")
        idx = jnp.asarray(pages, jnp.int32)
        self.k_pages = _serving_write_pages(self.k_pages, idx, k)
        self.v_pages = _serving_write_pages(self.v_pages, idx, v)
        count_dispatch("_serving_write_pages", 2)

    # -------------------------------------------------------- conv state

    def write_state(self, slot: int, state) -> None:
        """A row's conv state ``[conv layers, conv_L_cache - 1, hidden]``
        (its prefill's, or a dump's) into batch slot ``slot``, whole: what
        the slot's last row left there is never read again."""
        want = self.conv_state.shape[:2] + self.conv_state.shape[3:]
        if tuple(state.shape) != want:
            raise ValueError(f"conv state {tuple(state.shape)} does not fit "
                             f"this cache ({want}: conv layers, positions "
                             f"kept, hidden)")
        self.conv_state = _serving_write_state(
            self.conv_state, np.int32(slot), jnp.asarray(state))
        count_dispatch("_serving_write_state")

    def gather_state(self, slot: int):
        """Batch slot ``slot``'s conv state to the host, as
        :meth:`write_state` takes it: the dump's payload beside the pages."""
        return np.asarray(self.conv_state[:, :, slot])

    # ------------------------------------------------------------ defrag

    def defrag(self) -> Dict[int, int]:
        """Compact live pages to the front; returns {old: new} so the
        caller can rewrite block tables. A no-op ({}), when already
        compact. One gather-permute per buffer — O(P), no per-page
        copies. Conv state is a slot's, not a page's: it stays."""
        live = self.alloc.live_pages()
        mapping = {old: new for new, old in enumerate(live)}
        if all(old == new for old, new in mapping.items()):
            return {}
        taken = set(live)
        perm = list(live)
        perm.extend(p for p in range(self.num_pages) if p not in taken)
        perm.append(self.trash_page)
        idx = jnp.asarray(perm, jnp.int32)
        self.k_pages = jnp.take(self.k_pages, idx, axis=1)
        self.v_pages = jnp.take(self.v_pages, idx, axis=1)
        for owner in self.alloc.owners():
            self.alloc._owned[owner] = [
                mapping[p] for p in self.alloc._owned[owner]]
        n_live = len(live)
        self.alloc._free = list(range(self.num_pages - 1, n_live - 1, -1))
        return mapping
