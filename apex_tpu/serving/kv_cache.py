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
    "page_hbm_bytes",
]


def page_hbm_bytes(cfg, page_size: int, dtype=None) -> int:
    """Modeled HBM bytes of ONE page: k + v across all cache layers."""
    dtype = cfg.dtype if dtype is None else dtype
    itemsize = jnp.dtype(dtype).itemsize
    return (2 * cfg.cache_layers * page_size * cfg.num_kv_heads
            * cfg.head_dim * itemsize)


@dataclasses.dataclass(frozen=True)
class PageBudget:
    """The derivation trail of a page budget (kept for telemetry/docs —
    a budget that can't explain itself can't be debugged)."""

    pages: int
    page_bytes: int          # modeled bytes per page
    ratio: float             # measured/modeled correction (1.0 = none)
    hbm_bytes: int           # device HBM limit used
    watermark_bytes: int     # bytes the device already holds, subtracted
    usable_bytes: int        # hbm * safety - watermark (floored at 0)
    safety: float


def derive_page_budget(cfg, page_size: int, *,
                       hbm_bytes: Optional[int] = None,
                       watermark_bytes: Optional[int] = None,
                       priors: Optional[dict] = None,
                       safety: float = 0.90,
                       dtype=None) -> PageBudget:
    """Page budget from the live memory tier.

    ``pages = floor((hbm × safety − watermark) / (page_bytes × ratio))``.
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
    usable = max(0, int(hbm_bytes * safety) - int(watermark_bytes))
    pages = int(usable // max(1, int(math.ceil(page_bytes * ratio))))
    return PageBudget(pages=pages, page_bytes=page_bytes, ratio=ratio,
                      hbm_bytes=int(hbm_bytes),
                      watermark_bytes=int(watermark_bytes),
                      usable_bytes=usable, safety=safety)


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


@functools.partial(jax.jit, donate_argnums=(0,))
def _serving_write_pages(buf, idx, payload):
    """``buf[:, idx] = payload`` on the donated buffer, so in place: the
    un-jitted ``.at[:, idx].set`` allocated a second whole buffer for as
    long as the write ran, which does not fit once the cache is most of
    the chip (a looped stack's is). ``payload`` is
    ``[L, n * page_size, nkv, d]`` (prefill's) or ``[L, n, page_size, nkv,
    d]`` (a dump's). One compile per number of pages, named apart from the
    decode step for the recompile listener."""
    pages = payload.astype(buf.dtype).reshape(
        buf.shape[0], idx.shape[0], *buf.shape[2:])
    return buf.at[:, idx].set(pages)


class PagedKVCache:
    """The device-side paged cache + its allocator.

    Buffers are ``[L, P + 1, page_size, nkv, d]`` in ``cfg.dtype``, ``L``
    being :attr:`layers` (``cfg.cache_layers``); the
    extra page at index ``P`` (:attr:`trash_page`) absorbs inactive-slot
    scatter writes. The scheduler donates both buffers into the decode
    jit each step and stores the outputs back here.
    """

    def __init__(self, cfg, num_pages: int, page_size: int, dtype=None):
        self.cfg = cfg
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.dtype = cfg.dtype if dtype is None else dtype
        self.alloc = PageAllocator(self.num_pages)
        self.layers = int(cfg.cache_layers)
        shape = (self.layers, self.num_pages + 1, self.page_size,
                 cfg.num_kv_heads, cfg.head_dim)
        self.k_pages = jnp.zeros(shape, self.dtype)
        self.v_pages = jnp.zeros(shape, self.dtype)

    @property
    def trash_page(self) -> int:
        return self.num_pages

    def utilization(self) -> float:
        return self.alloc.num_used / self.num_pages

    def hbm_bytes(self) -> int:
        return 2 * int(np.prod(self.k_pages.shape)) * jnp.dtype(
            self.dtype).itemsize

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

    # ------------------------------------------------------------ defrag

    def defrag(self) -> Dict[int, int]:
        """Compact live pages to the front; returns {old: new} so the
        caller can rewrite block tables. A no-op ({}), when already
        compact. One gather-permute per buffer — O(P), no per-page
        copies."""
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
