"""Per-kernel tiling search spaces + the untuned default geometries.

This module is the single place tile/block *numbers* are allowed to live
outside ``ops/pallas_config.py`` (the ``hardcoded-tile-size`` AST lint
enforces exactly that): every Pallas kernel's candidate tilings are
declared here, generated within the analyzer's VMEM-lint budget
(:func:`apex_tpu.ops.pallas_config.device_vmem_bytes`) so no candidate
the tuner sweeps can be a VMEM-overflow compile bomb, and every kernel's
*untuned* fallback geometry is a function here too — the same tables
serve dispatch defaults, the tuner sweep, and the interpret-mode parity
tests (which must cover every candidate the sweep can emit).

Shape buckets: tuning results are keyed by a coarse shape bucket, not the
exact shape — ceil-power-of-2 on the data-volume dims (a 300M and a 350M
flat buffer share a tile) and exact on the dims tiles directly depend on
(head_dim, hidden). :func:`shape_bucket` is the one implementation.
"""

from __future__ import annotations

import math

from apex_tpu.ops import pallas_config

# Every kernel the tuner knows. flash fwd/bwd are separate search
# problems (different VMEM residency, different best tiles — the shipped
# defaults were 512 vs 256); both map onto the single 'flash_attention'
# dispatch verdict in pallas_config.KNOWN_KERNELS. fp8_cast is the O4
# fused cast-and-scale pass (ops/fp8_cast_kernel.py).
KERNELS = ("flat_adam", "flash_attention_fwd", "flash_attention_bwd",
           "layer_norm", "rms_norm", "fused_softmax", "fp8_cast")

# TPU min-tile geometry (pallas_guide.md tiling table): lane dim is
# always 128; fp32 sublane multiple is 8. Candidates below never go
# under these.
_LANE = 128
_SUBLANE = 8

# Fraction of the per-core VMEM budget a kernel's resident blocks may
# use: double-buffered pipelining needs ~2x the block residency, plus
# headroom for Mosaic's own scratch — same planning stance as the
# pallas-block VMEM check in apex_tpu.analysis.
_VMEM_FRACTION = 0.5


def _vmem_budget(device_kind=None) -> int:
    return int(pallas_config.device_vmem_bytes(device_kind)
               * _VMEM_FRACTION)


def _ceil_pow2(n: int) -> int:
    return 1 << max(0, int(n - 1).bit_length())


def shape_bucket(kernel: str, **dims) -> str:
    """Deterministic cache-key bucket for ``kernel`` at ``dims``.

    flat_adam buckets by ceil-pow2 buffer size; flash by ceil-pow2
    (sq, sk) with exact d; norms and fused_softmax by ceil-pow2 rows
    with exact h / sk. A tuned tile is reused for every shape landing in
    the same bucket.
    """
    if kernel in ("flat_adam", "fp8_cast"):
        return f"n~{_ceil_pow2(dims['n'])}"
    if kernel in ("flash_attention_fwd", "flash_attention_bwd"):
        return (f"sq~{_ceil_pow2(dims['sq'])},"
                f"sk~{_ceil_pow2(dims['sk'])},d={dims['d']}")
    if kernel in ("layer_norm", "rms_norm"):
        return f"rows~{_ceil_pow2(dims['rows'])},h={dims['h']}"
    if kernel == "fused_softmax":
        return f"sk~{_ceil_pow2(dims['sk'])}"
    raise ValueError(f"unknown kernel {kernel!r}; valid: {list(KERNELS)}")


# --------------------------------------------------------- candidate sets


def _flat_adam_vmem(block_rows: int, cols: int) -> int:
    # 5 input blocks (scalars negligible) + 3 output blocks, fp32-sized
    # (p may be bf16 — bound with fp32), double-buffered by the caller's
    # _VMEM_FRACTION.
    return block_rows * cols * 4 * 8


def flat_adam_candidates(n: int, device_kind=None) -> list:
    """(block_rows, cols) sweep for the flat Adam slab at buffer size
    ``n``. The 1024-column width is itself swept (the fixed (rows, 1024)
    slab is the prime suspect for the measured 3.2x TPU inversion);
    multi-row grid steps (block_rows > 8) are in the sweep. Candidates
    whose whole slab would pad to more than ~2x the buffer are dropped —
    padding waste is HBM traffic the kernel pays and XLA does not."""
    budget = _vmem_budget(device_kind)
    out = []
    for cols in (128, 256, 512, 1024, 2048):
        rows = -(-n // cols)
        for block_rows in (8, 16, 32, 64, 128, 256, 512, 1024):
            if _flat_adam_vmem(block_rows, cols) > budget:
                continue
            padded = -(-rows // block_rows) * block_rows * cols
            if padded > max(2 * n, _SUBLANE * _LANE * 8):
                continue
            out.append({"block_rows": block_rows, "cols": cols})
    return out or [{"block_rows": _SUBLANE, "cols": _LANE}]


def _flash_fwd_vmem(bq: int, bk: int, d: int) -> int:
    # q + o tiles [bq, d], k + v tiles [bk, d], fp32 score block
    # [bq, bk], m/l/acc scratch ([bq, 1] x2 + [bq, d]) — all fp32.
    return 4 * (2 * bq * d + 2 * bk * d + bq * bk + 2 * bq + bq * d)


def _flash_bwd_vmem(bq: int, bk: int, d: int) -> int:
    # worst of the dq / dkv kernels: q/k/v/do tiles + p/dp/ds blocks +
    # two [bk, d] accumulators, fp32.
    return 4 * (4 * bq * d + 2 * bk * d + 3 * bq * bk + 2 * bk * d
                + 2 * bq)


def flash_candidates(kind: str, sq: int, sk: int, d: int,
                     device_kind=None) -> list:
    """(block_q, block_kv) sweep for the flash ``kind`` pass. The kernel
    halves a tile toward a divisor of the sequence and pads the sequence
    past that (``flash_attention._tile``), so a candidate can never
    produce a non-dividing block at runtime; the VMEM filter here keeps
    the sweep compile-safe."""
    if kind not in ("fwd", "bwd"):
        raise ValueError(f"flash kind must be fwd/bwd, got {kind!r}")
    vmem = _flash_fwd_vmem if kind == "fwd" else _flash_bwd_vmem
    budget = _vmem_budget(device_kind)
    out = []
    for bq in (128, 256, 512, 1024):
        for bk in (128, 256, 512, 1024):
            if bq > max(sq, _LANE) or bk > max(sk, _LANE):
                continue
            if vmem(bq, bk, d) > budget:
                continue
            out.append({"block_q": bq, "block_kv": bk})
    return out or [{"block_q": _LANE, "block_kv": _LANE}]


def _fp8_cast_vmem(block_rows: int, cols: int) -> int:
    # x block fp32 in + fp8 out + the fp32 compute copy live at once;
    # the (1, 1) scale/amax blocks are noise. 2x headroom rides the
    # caller's _VMEM_FRACTION like every other kernel here.
    return block_rows * cols * (4 + 1 + 4)


def fp8_cast_candidates(n: int, device_kind=None) -> list:
    """(block_rows, cols) sweep for the fused fp8 cast-and-scale slab
    over an ``n``-element buffer (ops/fp8_cast_kernel.py). Same slab
    rules as flat_adam — padding capped at ~2x the buffer — except the
    row floor is 32: the fp8 OUTPUT's min tile is (32, 128)
    (pallas_guide.md dtype table), so an 8-row block that fp32 would
    accept is a Mosaic reject for an f8 store."""
    budget = _vmem_budget(device_kind)
    out = []
    for cols in (128, 256, 512, 1024, 2048):
        rows = -(-n // cols)
        for block_rows in (32, 64, 128, 256, 512, 1024):
            if _fp8_cast_vmem(block_rows, cols) > budget:
                continue
            padded = -(-rows // block_rows) * block_rows * cols
            if padded > max(2 * n, 32 * _LANE * 8):
                continue
            out.append({"block_rows": block_rows, "cols": cols})
    return out or [{"block_rows": 32, "cols": _LANE}]


def norm_candidates(kernel: str, rows: int, h: int,
                    device_kind=None) -> list:
    """Row-block sweep for layer_norm / rms_norm. The backward holds ~5
    fp32 block x h temps live (measured; see ops/layer_norm.py) — bound
    candidates by that so one tuned block serves fwd and bwd."""
    del kernel
    budget = _vmem_budget(device_kind)
    out = []
    for block in (8, 16, 32, 64, 128, 256, 512):
        if block * h * 4 * 5 > budget:
            continue
        if block > max(rows, _SUBLANE):
            continue
        out.append({"block_rows": block})
    return out or [{"block_rows": _SUBLANE}]


def softmax_candidates(sk: int, device_kind=None) -> list:
    """k-block sweep for the two-pass blocked fused softmax (long rows).
    x streams through VMEM twice; the resident block is [1, rows, bk]
    fp32 with rows >= 8."""
    budget = _vmem_budget(device_kind)
    out = []
    for bk in (512, 1024, 2048, 4096):
        if bk > max(sk, _LANE) or bk * _SUBLANE * 4 * 3 > budget:
            continue
        out.append({"block_k": bk})
    return out or [{"block_k": 512}]


def candidates(kernel: str, device_kind=None, **dims) -> list:
    """The full candidate list for ``kernel`` at ``dims`` — the one
    enumeration the tuner sweeps and the parity tests replay."""
    if kernel == "flat_adam":
        return flat_adam_candidates(dims["n"], device_kind)
    if kernel == "flash_attention_fwd":
        return flash_candidates("fwd", dims["sq"], dims["sk"], dims["d"],
                                device_kind)
    if kernel == "flash_attention_bwd":
        return flash_candidates("bwd", dims["sq"], dims["sk"], dims["d"],
                                device_kind)
    if kernel in ("layer_norm", "rms_norm"):
        return norm_candidates(kernel, dims["rows"], dims["h"],
                               device_kind)
    if kernel == "fused_softmax":
        return softmax_candidates(dims["sk"], device_kind)
    if kernel == "fp8_cast":
        return fp8_cast_candidates(dims["n"], device_kind)
    raise ValueError(f"unknown kernel {kernel!r}; valid: {list(KERNELS)}")


# ------------------------------------------------------ untuned defaults


def default_flat_adam_geometry(n: int) -> tuple:
    """(block_rows, cols) when no tuned entry exists. Unlike the old
    module constants (a fixed (512, 1024) slab, 8-row pad for anything
    smaller — a scalar bias padded to 8x1024 fp32 x4 buffers), the pad
    block follows the actual leaf size: cols shrinks to the smallest
    lane multiple that keeps the slab near-square-ish, and block_rows
    caps padding waste at ~25% + one block."""
    n = max(int(n), 1)
    cols = _LANE
    while cols < 1024 and n >= cols * _SUBLANE * 2:
        cols *= 2
    rows = -(-n // cols)
    block_rows = _SUBLANE
    for cand in (1024, 512, 256, 128, 64, 32, 16, _SUBLANE):
        if cand > rows and cand > _SUBLANE:
            continue
        if _flat_adam_vmem(cand, cols) > _vmem_budget():
            continue
        padded = -(-rows // cand) * cand
        if padded - rows <= max(_SUBLANE, rows // 4):
            block_rows = cand
            break
    return block_rows, cols


def default_norm_row_block(rows: int, h: int, f32_temps: int) -> int:
    """Largest ladder block whose fp32 scratch fits the scoped budget —
    the pre-tuner heuristic from ops/layer_norm.py, now living in the
    search-space tables. 0 = even the smallest block busts VMEM (caller
    takes the jnp path)."""
    budget = _vmem_budget() * 3 // 2  # ~12 MiB of the 16 MiB figure
    cap = budget // (max(h, 1) * 4 * max(f32_temps, 1))
    if cap < _SUBLANE:
        return 0
    best = _SUBLANE
    for cand in (256, 128, 64, 32, 16, _SUBLANE):
        if cand > cap:
            continue
        if rows % cand == 0:
            return cand
        best = max(best, cand)
    return best


def default_softmax_block_k() -> int:
    """k-block for the long-row two-pass fused softmax (the old
    fused_softmax._BLOCKED_BK module constant, routed here)."""
    return 2048


def default_fp8_cast_geometry(n: int) -> tuple:
    """(block_rows, cols) for the fp8 cast-and-scale slab when no tuned
    entry exists: the flat_adam sizing ladder with the row floor raised
    to the fp8 (32, 128) min tile, padding waste bounded the same way."""
    n = max(int(n), 1)
    cols = _LANE
    while cols < 1024 and n >= cols * 32 * 2:
        cols *= 2
    rows = -(-n // cols)
    block_rows = 32
    for cand in (1024, 512, 256, 128, 64, 32):
        if cand > rows and cand > 32:
            continue
        if _fp8_cast_vmem(cand, cols) > _vmem_budget():
            continue
        padded = -(-rows // cand) * cand
        if padded - rows <= max(32, rows // 4):
            block_rows = cand
            break
    return block_rows, cols
