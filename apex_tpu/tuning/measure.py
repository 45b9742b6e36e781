"""Candidate measurement: on-device races on TPU, roofline model on CPU.

On a TPU backend each candidate runs through the REAL dispatch path
(``tuning.geometry.override`` pins the tile, ``pallas_config.force``
selects Pallas vs the XLA fallback) and is timed with the scan-slope
timer (:func:`apex_tpu.runtime.timing.time_scanned` — most of these
kernels are shorter than one host dispatch, so a host loop would time
the dispatch, not the tile).

Off-TPU the roofline model from ``docs/kernel_cost_study.md`` is the
sanctioned fallback: ``t = max(flops/peak, bytes/bw) + grid_overhead``,
pure arithmetic, no RNG and no device — tuning stays deterministic and
testable in CI, and the ranking it produces is stable across runs by
construction. Roofline entries are recorded with ``source='roofline'``
and keyed to the CPU device kind, so they can never masquerade as
on-silicon evidence.
"""

from __future__ import annotations

from apex_tpu.tuning import geometry, search_space

# v5e roofline constants (docs/kernel_cost_study.md): peak bf16 compute
# and HBM bandwidth. Only RATIOS between candidates matter for ranking,
# so one generation's constants are fine as the portable CPU fallback.
PEAK_FLOPS = 197e12
HBM_BW = 819e9
# fixed cost per grid step (pipeline bubble + bookkeeping): what makes a
# 44k-step tiny-block sweep lose to a 700-step one on equal bytes, small
# enough that a well-blocked kernel's byte advantage still dominates
# (calibrated so the roofline reproduces every decision in the
# kernel-cost-study table: Pallas wins flash/norms/softmax, ties-then-
# loses flat_adam).
GRID_OVERHEAD_S = 2e-7

_ISZ = 2  # bf16 storage at the bench shapes; fp32 state modeled below


def backend_is_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"


def _ceil_div(a, b):
    return -(-a // b)


# ------------------------------------------------------ roofline models


def _roofline_flat_adam(params, dims):
    n = dims["n"]
    br, cols = params["block_rows"], params["cols"]
    rows = _ceil_div(n, cols)
    padded = _ceil_div(rows, br) * br * cols
    steps = padded // (br * cols)
    bytes_ = padded * 4 * 7  # g/p/m/v in + delta/m/v out, fp32 state
    return bytes_ / HBM_BW + steps * GRID_OVERHEAD_S


def _roofline_flat_adam_xla(dims):
    # XLA's fused elementwise chain reads/writes exactly the unpadded
    # buffer — no fusion left to beat (cost-study flat_adam row).
    return dims["n"] * 4 * 7 / HBM_BW


def _flash_dims(dims):
    return (dims.get("bh", 64), dims["sq"], dims["sk"], dims["d"],
            dims.get("causal", True))


def _roofline_flash(kind, params, dims):
    bh, sq, sk, d, causal = _flash_dims(dims)
    bq, bk = params["block_q"], params["block_kv"]
    nq, nk = _ceil_div(sq, bq), _ceil_div(sk, bk)
    frac = 0.5 if causal else 1.0
    flops = 4 * bh * sq * sk * d * frac
    # q/o ride once; k+v re-stream once per q block (the tile knob)
    io = bh * _ISZ * (2 * sq * d + nq * 2 * sk * d)
    steps = bh * nq * nk
    if kind == "bwd":
        flops *= 2.5  # dq + dkv kernels: 5 matmuls vs the fwd's 2
        io += bh * _ISZ * (3 * sq * d + nk * 2 * sq * d + 4 * sk * d)
        steps *= 2
    return max(flops / PEAK_FLOPS, io / HBM_BW) \
        + steps * frac * GRID_OVERHEAD_S


def _roofline_flash_xla(kind, dims):
    bh, sq, sk, d, causal = _flash_dims(dims)
    frac = 0.5 if causal else 1.0
    flops = 4 * bh * sq * sk * d * frac * (2.5 if kind == "bwd" else 1.0)
    # the fallback materializes the [sq, sk] score tensor and streams it
    # through 4 (fwd) / 8 (bwd) reduction fusions (cost-study flash rows)
    passes = 8 if kind == "bwd" else 4
    io = bh * _ISZ * ((4 if kind == "bwd" else 3) * (sq + sk) * d
                      + passes * sq * sk * frac)
    return max(flops / PEAK_FLOPS, io / HBM_BW)


def _roofline_norm(params, dims):
    rows, h = dims["rows"], dims["h"]
    block = params["block_rows"]
    padded = _ceil_div(rows, block) * block
    bytes_ = padded * h * _ISZ * 2 + padded * 4 * 2  # x in, y out, stats
    return bytes_ / HBM_BW + (padded // block) * GRID_OVERHEAD_S


def _roofline_norm_xla(dims):
    # measured-fusion column: the proxy compiler runs LN fwd as ~3
    # h-sized passes (1.5x the single-pass kernel's traffic)
    return dims["rows"] * dims["h"] * _ISZ * 3 / HBM_BW


def _roofline_softmax(params, dims):
    sk = dims["sk"]
    rows = dims.get("rows", 1024)
    bk = params["block_k"]
    # two-pass blocked kernel: x streams twice, y written once; the row
    # block shrinks as bk grows (fused_softmax sizes it off the same
    # ~2 MiB VMEM row budget), which is the bk tradeoff being swept
    bq = max(search_space._SUBLANE, (2 << 20) // (4 * bk))
    bytes_ = rows * sk * _ISZ * 3
    steps = _ceil_div(rows, bq) * _ceil_div(sk, bk) * 2
    return bytes_ / HBM_BW + steps * GRID_OVERHEAD_S


def _roofline_softmax_xla(dims):
    rows = dims.get("rows", 1024)
    return rows * dims["sk"] * _ISZ * 4 / HBM_BW


def _roofline_fp8_cast(params, dims):
    n = dims["n"]
    br, cols = params["block_rows"], params["cols"]
    rows = _ceil_div(n, cols)
    padded = _ceil_div(rows, br) * br * cols
    bytes_ = padded * (4 + 1)  # fp32 in, fp8 out; scale/amax are noise
    return bytes_ / HBM_BW + (padded // (br * cols)) * GRID_OVERHEAD_S


def _roofline_fp8_cast_xla(dims):
    # XLA runs the quantize (scale+clip+cast) and the amax reduction as
    # two fusions over the unpadded buffer: the input streams twice
    # (cost-study reduction-fusion stance) — the one-read fusion is the
    # kernel's whole advantage
    return dims["n"] * (2 * 4 + 1) / HBM_BW


def roofline(kernel, params, dims) -> float:
    """Modeled seconds for the Pallas kernel at ``params``."""
    if kernel == "flat_adam":
        return _roofline_flat_adam(params, dims)
    if kernel == "flash_attention_fwd":
        return _roofline_flash("fwd", params, dims)
    if kernel == "flash_attention_bwd":
        return _roofline_flash("bwd", params, dims)
    if kernel in ("layer_norm", "rms_norm"):
        return _roofline_norm(params, dims)
    if kernel == "fused_softmax":
        return _roofline_softmax(params, dims)
    if kernel == "fp8_cast":
        return _roofline_fp8_cast(params, dims)
    raise ValueError(f"unknown kernel {kernel!r}")


def roofline_xla(kernel, dims) -> float:
    """Modeled seconds for the XLA fallback path."""
    if kernel == "flat_adam":
        return _roofline_flat_adam_xla(dims)
    if kernel == "flash_attention_fwd":
        return _roofline_flash_xla("fwd", dims)
    if kernel == "flash_attention_bwd":
        return _roofline_flash_xla("bwd", dims)
    if kernel in ("layer_norm", "rms_norm"):
        return _roofline_norm_xla(dims)
    if kernel == "fused_softmax":
        return _roofline_softmax_xla(dims)
    if kernel == "fp8_cast":
        return _roofline_fp8_cast_xla(dims)
    raise ValueError(f"unknown kernel {kernel!r}")


# ---------------------------------------------------- live measurement


def _live_runner(kernel, dims):
    """(make_fn, carry, chain, k) for time_scanned — the same on-device
    scan-slope construction bench_kernels uses, per kernel."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(0)
    if kernel == "flat_adam":
        n = dims["n"]
        g = jax.random.normal(key, (n,), jnp.float32) * 1e-3
        p = jax.random.normal(jax.random.fold_in(key, 1), (n,),
                              jnp.float32)
        m = jnp.zeros((n,), jnp.float32)
        v = jnp.zeros((n,), jnp.float32)

        def make_fn():
            from apex_tpu.optimizers import _math
            from apex_tpu.ops import pallas_config
            from apex_tpu.ops.fused_adam_kernel import adam_flat_pallas

            def step(g, p, m, v):
                if pallas_config.use_pallas("flat_adam"):
                    # adam_flat_pallas resolves the active override into
                    # the inner jit's STATIC key per call — each
                    # candidate races its own compiled tile, never the
                    # first trace's
                    d, mo, vo = adam_flat_pallas(
                        g, p, m, v, jnp.float32(1e-3), jnp.float32(2.0),
                        b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.01,
                        adam_w_mode=True, bias_correction=True,
                        interpret=pallas_config.interpret())
                else:
                    d, mo, vo = _math.adam_step(
                        g, p, m, v, lr=1e-3, b1=0.9, b2=0.999, eps=1e-8,
                        weight_decay=0.01, adam_w_mode=True, step=2.0,
                        bias_correction=True)
                return g, p + d, mo, vo

            return step

        return make_fn, (g, p, m, v), (lambda c, step: step(*c)), 8

    if kernel in ("flash_attention_fwd", "flash_attention_bwd"):
        bh, sq, sk, d, causal = _flash_dims(dims)
        b, h = max(bh // 16, 1), min(bh, 16)
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (b, sq, h, d), jnp.bfloat16)
        kk_ = jax.random.normal(kk, (b, sk, h, d), jnp.bfloat16)
        vv = jax.random.normal(kv, (b, sk, h, d), jnp.bfloat16)

        def make_fwd():
            from apex_tpu.ops.flash_attention import flash_attention

            return lambda q, k, v: flash_attention(q, k, v,
                                                   causal=causal)

        def make_bwd():
            from apex_tpu.ops.flash_attention import flash_attention

            return jax.grad(
                lambda q, k, v: jnp.sum(
                    flash_attention(q, k, v, causal=causal)
                    .astype(jnp.float32)), argnums=(0, 1, 2))

        if kernel.endswith("fwd"):
            chain = lambda c, step: (step(*c), c[1], c[2])  # noqa: E731
            return make_fwd, (q, kk_, vv), chain, 8
        return make_bwd, (q, kk_, vv), (lambda c, step: step(*c)), 8

    if kernel in ("layer_norm", "rms_norm"):
        rows, h = dims["rows"], dims["h"]
        x = jax.random.normal(key, (rows, h), jnp.bfloat16)
        w = jnp.ones((h,), jnp.float32)
        b = jnp.zeros((h,), jnp.float32)

        def make_fn():
            from apex_tpu.ops.layer_norm import layer_norm, rms_norm

            if kernel == "layer_norm":
                return lambda x: layer_norm(x, w, b, (h,))
            return lambda x: rms_norm(x, w, (h,))

        return make_fn, x, (lambda c, step: step(c)), 32

    if kernel == "fused_softmax":
        rows, sk = dims.get("rows", 256), dims["sk"]
        x = jax.random.normal(key, (8, rows, sk), jnp.bfloat16)

        def make_fn():
            from apex_tpu.transformer.functional.fused_softmax import (
                scaled_upper_triang_masked_softmax,
            )

            return lambda x: scaled_upper_triang_masked_softmax(
                x, None, 1.0)

        return make_fn, x, (lambda c, step: step(c)), 16

    if kernel == "fp8_cast":
        n = dims["n"]
        x = jax.random.normal(key, (n,), jnp.float32)

        def make_fn():
            from apex_tpu.ops import precision

            def step(x):
                # dequantize back to the fp32 carry so the scan threads
                # the kernel's output (idempotent after iteration 1 —
                # fine for timing, the bytes still stream); the
                # sign(amax+1) factor is 1 but keeps the fused amax
                # output live against DCE
                y, amax = precision.quantize_fp8_stats(
                    x, jnp.float32(1.0))
                return y.astype(jnp.float32) * jnp.sign(amax + 1.0)

            return step

        return make_fn, x, (lambda c, step: step(c)), 16

    raise ValueError(f"unknown kernel {kernel!r}")


def live_runner(kernel, dims):
    """Build the measurement inputs ONCE per (kernel, dims) and reuse
    across the whole sweep — the flat_adam carry alone is ~5.7 GB of
    freshly-drawn arrays, which must not be regenerated per candidate
    inside a scarce live-TPU window."""
    return _live_runner(kernel, dims)


def measure_live(kernel, params, dims, runner=None) -> float:
    """Seconds per iteration of the Pallas path at ``params`` on the
    current (TPU) backend."""
    from apex_tpu.ops import pallas_config
    from apex_tpu.runtime import timing

    make_fn, carry, chain, k = runner or _live_runner(kernel, dims)
    with geometry.override(kernel, params):
        with pallas_config.force("on"):
            return timing.time_scanned(make_fn, carry, chain, k=k)


def measure_live_xla(kernel, dims, runner=None) -> float:
    """Seconds per iteration of the XLA fallback on the current
    backend."""
    from apex_tpu.ops import pallas_config
    from apex_tpu.runtime import timing

    make_fn, carry, chain, k = runner or _live_runner(kernel, dims)
    with pallas_config.force("off"):
        return timing.time_scanned(make_fn, carry, chain, k=k)


def measure(kernel, params, dims, live=None, runner=None) -> float:
    """Pallas-candidate seconds: live race on TPU, roofline elsewhere."""
    if live is None:
        live = backend_is_tpu()
    if live:
        return measure_live(kernel, params, dims, runner=runner)
    return roofline(kernel, params, dims)


def measure_xla(kernel, dims, live=None, runner=None) -> float:
    """XLA-fallback seconds under the same live/roofline policy."""
    if live is None:
        live = backend_is_tpu()
    if live:
        return measure_live_xla(kernel, dims, runner=runner)
    return roofline_xla(kernel, dims)
