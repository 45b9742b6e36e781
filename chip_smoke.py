#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that apex_tpu still starts on the chip.

    python chip_smoke.py

One process, one TPU host. Drives the system's main paths once, through the
entry points a user calls, at the full width of the models the repo
supports (weights random, from a seed), and checks what comes out:

- *kernels*  every Pallas kernel compiled by Mosaic against its jnp path,
             at the shapes the two models below use;
- *train*    GPT-2 345M (hidden 1024, 24 layers, vocab 50,304, S=1024,
             B=8): ``amp.initialize("O2")`` -> ``policy.cast_model`` ->
             ``gpt2.loss_fn(vocab_chunks=8)`` -> ``scaled_update(fused_adam)``,
             donated state, 8 steps on one fixed batch;
- *serve*    ``ServingEngine`` on ``llama.flagship_0p9b()`` with the live
             page budget, eight requests of 64/520/1024-token prompts,
             checked against ``models.generate.generate()``;
- *looped*   ``ServingEngine`` on a two-layer, four-pass llama stack at
             Ouro-2.6B's widths (eight cache layers behind two layers of
             weights), four requests, checked on logits against the plain
             reference ``perfbench/references/ouro_looped.py``;
- *afmoe*    ``ServingEngine`` on five layers at Trinity-Large-Preview's
             widths (a dense lead, four dropless expert layers holding 8 of
             256 sigmoid-routed experts beside a shared one; four sliding
             layers with a window of 512 and one full), prompts of 1,024 so
             that the windowed flash forward and a prefill cut by the window
             run, checked on logits against
             ``perfbench/references/afmoe.py``;
- *lfm2*     ``ServingEngine`` on the nine layers of
             ``perfbench/configs/lfm2_24b_a2b_d9.json`` at LFM2-24B-A2B's
             widths (seven gated short-convolution layers beside two
             attention layers, a dense lead, all 64 experts of eight expert
             layers held: 9.64 GiB of weights), four rows, prompts that end
             inside their bucket and one shorter than the convolution,
             checked on logits against ``perfbench/references/lfm2_moe.py``;
- *mesh*     (4+ chips) the same GPT-2 step on a dp=2 x tp=2 mesh, and a
             dp=4 DDP step through ``sync_autodiff_gradients``.

Any phase that raises, or whose check fails, ends the run with a non-zero
exit code. It refuses to run when jax selects anything but the TPU. The
last line of standard output is one JSON object naming the device. The
per-step wall times it prints are for information; none is a metric.
"""

from __future__ import annotations

import functools
import importlib.metadata
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from apex_tpu import amp
from apex_tpu.models import generate as gen
from apex_tpu.models import gpt2, llama
from apex_tpu.observability import recompile
from apex_tpu.ops import pallas_config
from apex_tpu.optimizers import fused_adam, opt_partition_specs
from apex_tpu.parallel import sync_autodiff_gradients
from apex_tpu.runtime.timing import sync
from apex_tpu.serving import ServingEngine, build_prefill
from apex_tpu.transformer.tensor_parallel.mappings import make_varying

_MOSAIC_CALL = "tpu_custom_call"
# exit code of a refusal to run (no TPU; a tuning cache in the way) — apart
# from 1 (a phase raised) and from the chip tool's own 2 and 3
EXIT_REFUSED = 4


def say(msg: str) -> None:
    print(msg, flush=True)


def _kernel_mode() -> str:
    """How the kernels run here: compiled ('on') unless a test put the
    process in interpret mode, which is how tier-1 runs these phases on
    the CPU. The kernels phase forces this mode against 'off'; the model
    phases run in the ambient mode ('auto' takes the compiled kernels on
    a TPU) and prove by HLO that it took them."""
    return "interpret" if pallas_config.mode() == "interpret" else "on"


def _expect_mosaic(text: str, *kernels: str) -> None:
    """The named Pallas kernels must appear in ``text`` (lowered or
    compiled HLO) as Mosaic custom calls exactly when the kernels are
    compiled — so a silent jnp path cannot pass, and the interpreter is
    not mistaken for the chip."""
    compiled = _kernel_mode() == "on"
    for kernel in kernels:
        found = _MOSAIC_CALL in text and kernel in text
        if found != compiled:
            raise AssertionError(
                f"Mosaic custom call {kernel!r} "
                f"{'missing from' if compiled else 'unexpectedly in'} the "
                f"HLO (kernel mode {_kernel_mode()!r})")


# ------------------------------------------------------------------ kernels

# Shapes the two models below hand the kernels (flash: B, S, q heads, kv
# heads, head dim — GQA 16/8 x 128 is flagship_0p9b, 16 x 64 over 1,024
# is GPT-2 345M's training attention; LayerNorm 8192 x 1024 and the
# [128, 1024, 1024] causal softmax are GPT-2 345M at B=8).
KERNEL_SHAPES = dict(
    flash=(2, 2048, 16, 8, 128), flash_gpt2=(4, 1024, 16, 16, 64),
    prefill_len=520,
    norm_rows=8192, ln_hidden=1024, rms_hidden=2048,
    causal_softmax=(128, 1024), masked_softmax=(64, 512),
    adam_n=1_000_000, fp8=(2048, 2048), gmm=(4096, 2048, 1536, 64))


def _pallas_vs_jnp(fn, *args):
    """``fn(*args)`` through the Pallas kernels and through the jnp path.
    Fresh lambdas each time: jit's trace cache is keyed on the function
    object, and the dispatch mode is read while tracing."""
    with pallas_config.force(_kernel_mode()):
        pallas_fn = jax.jit(lambda *a: fn(*a))
        _expect_mosaic(pallas_fn.lower(*args).as_text(), "apex_")
        got = sync(pallas_fn(*args))
    with pallas_config.force("off"):
        want = sync(jax.jit(lambda *a: fn(*a))(*args))
    return got, want


def _close(got, want, rtol=2e-2, atol=2e-2, what=""):
    # bf16 kernel vs fp32-ish jnp path: loose but real tolerance
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=rtol, atol=atol, err_msg=what)


def _close_flash_bwd(got, want, what, tol=5e-2, max_abs=0.5, frac=5e-4):
    """Flash bwd vs autodiff of the jnp path, delta-cancellation aware.

    The kernel uses the standard flash convention delta = sum(do * o) with
    o saved in bf16; autodiff of the materialized softmax cancels
    p*(dp - sum(p*dp)) EXACTLY for near-degenerate rows (causal row 0 sees
    one key -> softmax == [1]). The kernel's residual there is bounded by
    |do|*|o|*bf16_eps*sqrt(D), the same property as the CUDA flash kernels.
    So: elementwise tol for ~all elements, a bounded violating fraction,
    and a hard abs cap."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    diff = np.abs(got - want)
    n_viol = int((diff > tol + tol * np.abs(want)).sum())
    if n_viol > frac * diff.size or float(diff.max()) > max_abs:
        raise AssertionError(
            f"{what}: {n_viol}/{diff.size} elements beyond tol (allowed "
            f"{int(frac * diff.size)}), max abs {float(diff.max()):.4f} "
            f"(cap {max_abs})")


def _norm_grads_close(got, want, rows, what):
    """dx elementwise; dw/db are sums over ``rows`` of bf16-quantized
    grads — the two paths round y to different bf16 ulps, and
    sqrt(rows)-scaled quantization noise survives the reduction."""
    _close(got[0], want[0], rtol=5e-2, atol=5e-1, what=f"{what} dx")
    noise = float(4.0 * np.sqrt(rows) * 0.0078)
    for name, g, w in zip(("dw", "db"), got[1:], want[1:]):
        _close(g, w, rtol=5e-2, atol=noise, what=f"{what} {name}")


def _qkv(shape, seed, dtype=jnp.bfloat16):
    b, s, h, h_kv, d = shape
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(kq, (b, s, h, d), dtype),
            jax.random.normal(kk, (b, s, h_kv, d), dtype),
            jax.random.normal(kv, (b, s, h_kv, d), dtype))


def _sq_loss(fn):
    return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) ** 2)


def _kernel_checks(shapes):
    """(name, check) pairs; each check raises on a mismatch."""
    from apex_tpu.ops.flash_attention import flash_attention
    from apex_tpu.ops.fp8_cast_kernel import cast_and_scale_stats
    from apex_tpu.ops.layer_norm import layer_norm, rms_norm
    from apex_tpu.transformer.functional.fused_softmax import (
        scaled_masked_softmax,
        scaled_upper_triang_masked_softmax,
    )

    flash = shapes["flash"]
    b, s, h, h_kv, d = flash
    rows = shapes["norm_rows"]
    causal = functools.partial(flash_attention, causal=True)
    grad3 = lambda f: jax.grad(_sq_loss(f), argnums=(0, 1, 2))

    def flash_fwd():
        _close(*_pallas_vs_jnp(causal, *_qkv(flash, 0)), what="flash fwd")

    def flash_bwd():
        got, want = _pallas_vs_jnp(grad3(causal), *_qkv(flash, 1))
        for name, g, w in zip("qkv", got, want):
            _close_flash_bwd(g, w, f"flash d{name}")

    def flash_gpt2():
        # forward and backward at GPT-2's head width: bf16 to the MXU
        shape = shapes["flash_gpt2"]
        scaled = functools.partial(flash_attention, causal=True,
                                   scale=shape[-1] ** -0.5)
        _close(*_pallas_vs_jnp(scaled, *_qkv(shape, 5)), what="flash gpt2")
        got, want = _pallas_vs_jnp(grad3(scaled), *_qkv(shape, 6))
        for name, g, w in zip("qkv", got, want):
            _close_flash_bwd(g, w, f"flash gpt2 d{name}")

    def flash_prefill():
        # a prompt bucket that is no multiple of any block: the kernel
        # pads it to 128-row blocks and masks the padded keys
        shape = (1, shapes["prefill_len"], h, h_kv, d)
        _close(*_pallas_vs_jnp(causal, *_qkv(shape, 2)),
               what="flash prefill")

    def flash_varlen():
        lens = jnp.asarray([s] + [max(1, s // (i + 2)) for i in range(b - 1)],
                           jnp.int32)
        _close(*_pallas_vs_jnp(
            lambda q, k, v: flash_attention(q, k, v, kv_lens=lens),
            *_qkv((b, s, h, h, d), 3)), what="flash varlen")

    def flash_dropout():
        # the same counter-based mask on both paths -> grads must agree
        key = jax.random.PRNGKey(7)
        drop = lambda q, k, v: flash_attention(
            q, k, v, causal=True, dropout_p=0.25, dropout_key=key)
        got, want = _pallas_vs_jnp(grad3(drop), *_qkv(flash, 4))
        for name, g, w in zip("qkv", got, want):
            _close_flash_bwd(g, w, f"flash dropout d{name}")

    def ln_fwd_bwd():
        hid = shapes["ln_hidden"]
        x = jax.random.normal(jax.random.PRNGKey(5), (rows, hid),
                              jnp.bfloat16)
        w, bias = jnp.ones((hid,), jnp.float32), jnp.zeros((hid,),
                                                           jnp.float32)
        ln = lambda x, w, bias: layer_norm(x, w, bias, (hid,))
        _norm_grads_close(*_pallas_vs_jnp(grad3(ln), x, w, bias), rows,
                          "layer_norm")

    def rms_fwd_bwd():
        hid = shapes["rms_hidden"]
        x = jax.random.normal(jax.random.PRNGKey(6), (rows, hid),
                              jnp.bfloat16)
        w = jnp.ones((hid,), jnp.float32)
        rms = lambda x, w: rms_norm(x, w, (hid,))
        _close(*_pallas_vs_jnp(rms, x, w), what="rms fwd")
        _norm_grads_close(*_pallas_vs_jnp(
            jax.grad(_sq_loss(rms), argnums=(0, 1)), x, w), rows, "rms_norm")

    def ln_odd_rows():
        hid = shapes["ln_hidden"]
        x = jax.random.normal(jax.random.PRNGKey(8), (13, hid), jnp.bfloat16)
        w, bias = jnp.ones((hid,), jnp.float32), jnp.zeros((hid,),
                                                           jnp.float32)
        _close(*_pallas_vs_jnp(
            lambda x: layer_norm(x, w, bias, (hid,)), x), what="ln odd rows")

    def causal_softmax():
        bh, sm = shapes["causal_softmax"]
        x = jax.random.normal(jax.random.PRNGKey(9), (bh, sm, sm),
                              jnp.bfloat16)
        _close(*_pallas_vs_jnp(
            lambda x: scaled_upper_triang_masked_softmax(x, None, 1.0), x),
            what="causal softmax")

    def masked_softmax():
        bh, sm = shapes["masked_softmax"]
        x = jax.random.normal(jax.random.PRNGKey(10),
                              (4, bh // 4, sm, sm), jnp.bfloat16)
        mask = jax.random.uniform(jax.random.PRNGKey(11),
                                  (4, 1, sm, sm)) > 0.8
        _close(*_pallas_vs_jnp(
            lambda x: scaled_masked_softmax(x, mask, 0.5), x),
            what="masked softmax")

    def softmax_bwd():
        # one backward kernel behind both custom_vjp rules: the causal
        # one square, the masked one rectangular
        bh, sm = shapes["causal_softmax"]
        x = jax.random.normal(jax.random.PRNGKey(15), (bh, sm, sm),
                              jnp.bfloat16)
        causal_sm = jax.grad(_sq_loss(
            lambda x: scaled_upper_triang_masked_softmax(x, None, 0.125)))
        with pallas_config.force(_kernel_mode()):
            _expect_mosaic(jax.jit(causal_sm).lower(x).as_text(),
                           "apex_softmax_bwd")
        _close(*_pallas_vs_jnp(causal_sm, x), atol=2e-3,
               what="causal softmax dx")
        bh, sm = shapes["masked_softmax"]
        x = jax.random.normal(jax.random.PRNGKey(16),
                              (4, bh // 4, sm // 2, sm), jnp.bfloat16)
        mask = jax.random.uniform(jax.random.PRNGKey(17),
                                  (4, 1, sm // 2, sm)) > 0.8
        _close(*_pallas_vs_jnp(jax.grad(_sq_loss(
            lambda x: scaled_masked_softmax(x, mask, 0.5))), x), atol=2e-3,
            what="masked softmax dx")

    def flat_adam():
        # not the default since the XLA chain won the cost study, but it
        # must still execute when forced on: scalar (1, 4) block + slab
        # padding are Mosaic-sensitive
        params = {"a": jax.random.normal(jax.random.PRNGKey(12),
                                         (shapes["adam_n"],)),
                  "b": jax.random.normal(jax.random.PRNGKey(13), (137,))}
        grads = jax.tree_util.tree_map(lambda p: p * 1e-2, params)

        def one_step(use_kernel):
            tx = fused_adam(lr=1e-3, weight_decay=0.01, flat=True,
                            use_kernel=use_kernel)
            step = jax.jit(lambda g, s, p: tx.update(g, s, p)[0])
            state = tx.init(params)
            if use_kernel:
                _expect_mosaic(step.lower(grads, state, params).as_text(),
                               "apex_flat_adam")
            return sync(step(grads, state, params))

        with pallas_config.force(_kernel_mode()):
            got = one_step(True)
        want = one_step(False)
        for k in params:
            _close(got[k], want[k], rtol=1e-5, atol=1e-6, what=f"adam {k}")

    def fp8_cast():
        x = jax.random.normal(jax.random.PRNGKey(14), shapes["fp8"],
                              jnp.bfloat16) * 3.0
        cast = lambda x: cast_and_scale_stats(
            x, jnp.float32(17.0), jnp.float8_e4m3fn, 448.0)
        (got_y, got_amax), (want_y, want_amax) = _pallas_vs_jnp(cast, x)
        if got_y.dtype != jnp.float8_e4m3fn:
            raise AssertionError(f"fp8 cast produced {got_y.dtype}")
        # one E4M3 ulp (3 mantissa bits) wherever the two converters round
        # a tie differently; amax is a max of exact bf16 values
        _close(got_y, want_y, rtol=0.13, atol=2.0 ** -9, what="fp8 y")
        _close(got_amax, want_amax, rtol=0, atol=0, what="fp8 amax")

    def grouped_matmul():
        # the dropless expert layers' product: sorted rows on some of a
        # layer's groups, the layer found at a traced offset in a stack of
        # three; rows of no group are unspecified and left out
        from apex_tpu.ops.grouped_matmul import gmm
        m, k, n, held = shapes["gmm"]
        x = jax.random.normal(jax.random.PRNGKey(18), (m, k), jnp.bfloat16)
        w = jax.random.normal(jax.random.PRNGKey(19), (3 * held, k, n),
                              jnp.bfloat16) * k ** -0.5
        own = np.random.default_rng(0).multinomial(
            m - m // 8, np.full(held, 1 / held)) * (np.arange(held) % 5 > 0)
        sizes = jnp.zeros(3 * held, jnp.int32).at[held:2 * held].set(own)
        with pallas_config.force(_kernel_mode()):
            product = jax.jit(lambda x, w, sizes, at: gmm(x, w, sizes, at,
                                                          held))
            _expect_mosaic(product.lower(x, w, sizes, jnp.int32(held))
                           .as_text(), "apex_gmm")
            got = sync(product(x, w, sizes, jnp.int32(held)))
        want = sync(jax.jit(jax.lax.ragged_dot)(x, w, sizes))
        rows = int(own.sum())
        _close(got[:rows], want[:rows], what="grouped matmul")

    return [("flash_fwd_causal_gqa", flash_fwd),
            ("flash_bwd_causal_gqa", flash_bwd),
            ("flash_fwd_bwd_gpt2", flash_gpt2),
            ("flash_fwd_prefill_len", flash_prefill),
            ("flash_varlen", flash_varlen),
            ("flash_dropout_fwd_bwd", flash_dropout),
            ("layer_norm_fwd_bwd", ln_fwd_bwd),
            ("rms_norm_fwd_bwd", rms_fwd_bwd),
            ("layer_norm_odd_rows", ln_odd_rows),
            ("causal_softmax", causal_softmax),
            ("masked_softmax", masked_softmax),
            ("softmax_bwd", softmax_bwd),
            ("flat_adam_kernel", flat_adam),
            ("fp8_cast_kernel", fp8_cast),
            ("grouped_matmul", grouped_matmul)]


def phase_kernels(shapes=KERNEL_SHAPES) -> dict:
    """Every Pallas kernel, compiled, against its jnp path."""
    checks = _kernel_checks(shapes)
    for name, check in checks:
        t0 = time.perf_counter()
        check()
        say(f"  PASS {name} ({time.perf_counter() - t0:.1f}s)")
    return {"checks": len(checks)}


# -------------------------------------------------------------------- train

TRAIN_LR = 1e-4
VOCAB_CHUNKS = 8


def _gpt2_setup(cfg, batch):
    """(handle, tx, init_state, batch): the O2 composition of
    tests/L1/l1_harness.py at ``cfg``. ``init_state()`` builds
    ``(params, opt_state, scaler_state)`` — fp32 master weights (the
    model copy is cast to bf16 inside the step), from a fixed seed."""
    handle = amp.initialize(opt_level="O2", verbosity=0)
    tx = fused_adam(lr=TRAIN_LR)

    def init_state():
        params = jax.tree_util.tree_map(
            lambda p: p.astype(jnp.float32),
            gpt2.init_params(jax.random.PRNGKey(0), cfg))
        return params, tx.init(params), handle.scaler.init()

    tokens = jax.random.randint(jax.random.PRNGKey(1),
                                (batch, cfg.max_seq_len), 0, cfg.vocab_size)
    return handle, tx, init_state, (tokens, jnp.roll(tokens, -1, axis=-1))


def _gpt2_step(handle, tx, cfg, tp_axis=None, reduction=None,
               overflow_axes=()):
    """``(params, opt_state, scaler_state, batch) -> (..., loss)``: one
    O2 step. ``reduction`` is the mesh's gradient scheme (``prepare`` the
    parameters, ``reduce`` gradients and loss); None on one chip."""

    def step(params, opt_state, sstate, batch):
        def scaled(p):
            if reduction is not None:
                p = reduction.prepare(p)
            loss = gpt2.loss_fn(handle.policy.cast_model(p), batch, cfg,
                                tp_axis=tp_axis, vocab_chunks=VOCAB_CHUNKS)
            return handle.scaler.scale_loss(loss, sstate), loss

        grads, loss = jax.grad(scaled, has_aux=True)(params)
        if reduction is not None:
            grads, loss = reduction.reduce(grads, loss)
        updates, opt_state, sstate, _ = handle.scaled_update(
            tx, grads, opt_state, params, sstate,
            overflow_reduce_axes=overflow_axes)
        return optax.apply_updates(params, updates), opt_state, sstate, loss

    return step


def _run_steps(compiled, state, batch, steps):
    """Drive ``steps`` donated steps of an AOT-compiled train step; returns
    (final state, losses). Nothing may compile after step 1."""
    listener = recompile.install()
    losses, compiles_after_first = [], None
    for i in range(steps):
        t0 = time.perf_counter()
        *state, loss = compiled(*state, batch)
        loss = float(sync(loss))
        losses.append(loss)
        say(f"  step {i}: loss {loss:.4f}  "
            f"({(time.perf_counter() - t0) * 1e3:.0f} ms wall, "
            f"for information)")
        if i == 0:
            compiles_after_first = listener.backend_compiles()
    extra = listener.backend_compiles() - compiles_after_first
    if extra:
        raise AssertionError(
            f"{extra} program(s) compiled after step 1: "
            f"{listener.compiles()}")
    return state, losses


def _check_losses(losses, lo, hi):
    if not all(np.isfinite(losses)):
        raise AssertionError(f"non-finite loss: {losses}")
    if not lo <= losses[0] <= hi:
        raise AssertionError(
            f"first loss {losses[0]:.4f} outside [{lo}, {hi}]")
    if not losses[-1] < losses[0]:
        raise AssertionError(
            f"loss did not fall: {losses[0]:.4f} -> {losses[-1]:.4f}")


def phase_train(cfg=None, batch=8, steps=8) -> dict:
    """GPT-2 345M, amp O2 + FusedAdam, donated state, one fixed batch."""
    cfg = cfg or gpt2.gpt2_345m()
    handle, tx, init_state, data = _gpt2_setup(cfg, batch)
    state = init_state()
    n_params = sum(p.size for p in jax.tree_util.tree_leaves(state[0]))
    say(f"  gpt2: {n_params / 1e6:.1f}M params, hidden {cfg.hidden_size}, "
        f"{cfg.num_layers} layers, vocab {cfg.vocab_size}, "
        f"B={batch} S={cfg.max_seq_len}")
    step = jax.jit(_gpt2_step(handle, tx, cfg), donate_argnums=(0, 1, 2))
    t0 = time.perf_counter()
    compiled = step.lower(*state, data).compile()
    say(f"  train step compiled in {time.perf_counter() - t0:.1f}s")
    _expect_mosaic(compiled.as_text(), "apex_ln_fwd", "apex_ln_bwd",
                   "apex_flash_fwd", "apex_flash_bwd_dq",
                   "apex_flash_bwd_dkv")
    state, losses = _run_steps(compiled, state, data, steps)
    # ln(vocab) plus half the init's logit variance (~1)
    ln_v = float(np.log(cfg.vocab_size))
    _check_losses(losses, ln_v - 0.35, ln_v + 1.2)
    skipped = int(state[2].overflows)
    if skipped >= steps:
        raise AssertionError(f"the loss scaler skipped all {steps} steps")
    say(f"  loss {losses[0]:.4f} -> {losses[-1]:.4f}; scaler skipped "
        f"{skipped}/{steps} steps, scale {float(state[2].loss_scale):.0f}")
    return {"first_loss": losses[0], "last_loss": losses[-1]}


# -------------------------------------------------------------------- serve

# (prompt length, new tokens): three shapes, so generate() compiles three
# programs; 520 is a prompt bucket no flash block divides
SERVE_MIX = ((64, 128), (520, 96), (1024, 64))
# A token may differ from generate()'s where bf16 reduction order decides
# a near-tie (one [8, h] decode batch against eight [1, h] ones; a page
# gather against a contiguous cache) — on a v5e 7 of 8 requests do, after
# 18-60 equal tokens. Then both tokens' logits, from the TRAINING forward
# on the shared prefix, must lie within this of the best. The logits come
# out of a bf16 matmul: the winning ones here are 4-8 in magnitude, where
# one bf16 step is 2^-5; four steps are allowed (two were seen), against a
# typical gap of ~4 between the best logit and an arbitrary one.
LOGIT_TIE_TOL = 2.0 ** -3


def _reference_logits(params, cfg, length):
    """Jitted ``(tokens [1, length], n) -> fp32 logits after n tokens`` by
    the TRAINING forward — the arbiter of a serving/generate() near-tie."""

    # the weights are an argument: closed over, they would be baked into
    # the executable as a constant too large for the compile cache to write
    @jax.jit
    def logits_at(params, tokens, n):
        logits = llama.forward(params, tokens, cfg, tp_axis=None,
                               cp_axis=None, remat=False)
        return jax.lax.dynamic_index_in_dim(logits[0], n - 1, axis=0,
                                            keepdims=False)

    return lambda prefix: np.asarray(logits_at(
        params,
        jnp.asarray(np.pad(prefix, (0, length - len(prefix)))[None]),
        np.int32(len(prefix))))


def phase_serve(cfg=None, mix=SERVE_MIX, requests=8, max_batch=8,
                page_size=8) -> dict:
    """Continuous batching on the flagship llama against generate()."""
    cfg = cfg or llama.flagship_0p9b()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    max_prompt = max(p for p, _ in mix)
    max_new = max(n for _, n in mix)
    listener = recompile.install()
    decode_compiles0 = listener.compiles("_decode_step")
    engine = ServingEngine(
        params, cfg, page_size=page_size, max_batch=max_batch,
        num_pages=None, max_prompt_len=max_prompt, max_new_cap=max_new)
    cache = engine.scheduler.cache
    stats = jax.devices()[0].memory_stats() or {}
    say(f"  page budget: {engine.page_budget}")
    say(f"  pages in use {cache.num_pages} (+1 trash), k_pages "
        f"{tuple(cache.k_pages.shape)} {cache.k_pages.dtype}: modeled "
        f"{cache.hbm_bytes() // 2} B, on device "
        f"{cache.k_pages.on_device_size_in_bytes()} B; device "
        f"bytes_in_use {stats.get('bytes_in_use')} peak "
        f"{stats.get('peak_bytes_in_use')}")
    rng = np.random.default_rng(0)
    prompts = {}
    for i in range(requests):
        p_len, new = mix[i % len(mix)]
        prompt = rng.integers(0, cfg.vocab_size, p_len, dtype=np.int32)
        prompts[engine.submit(prompt, new)] = (prompt, new)
    t0 = time.perf_counter()
    results = engine.run()      # raises if the decode step retraced
    say(f"  {len(results)} requests served in "
        f"{time.perf_counter() - t0:.1f}s wall (compiles included, for "
        f"information): {engine.scheduler.prefill_count} prefills, "
        f"{engine.scheduler.decode_steps} decode steps")
    if sorted(results) != sorted(prompts):
        raise AssertionError(
            f"finished {sorted(results)}, submitted {sorted(prompts)}")
    n_decode = listener.compiles("_decode_step") - decode_compiles0
    if n_decode != 1:
        raise AssertionError(f"decode step compiled {n_decode}x, not 1")
    for p_len, _ in mix:
        bucket = -(-p_len // page_size) * page_size
        _expect_mosaic(build_prefill(cfg, bucket).lower(
            params, {}, jnp.zeros((1, bucket), jnp.int32),
            np.int32(p_len)).as_text(), "apex_flash_fwd")

    # ---- the reference: generate(), one request at a time
    generate = jax.jit(gen.generate, static_argnums=(2, 3))
    ref_logits = None
    exact = ties = 0
    for rid, (prompt, new) in sorted(prompts.items()):
        got = results[rid]["tokens"]
        want = np.asarray(generate(params, jnp.asarray(prompt[None]),
                                   cfg, new))[0, len(prompt):].tolist()
        if len(got) != new:
            raise AssertionError(
                f"request {rid}: {len(got)} tokens, asked for {new}")
        if got == want:
            exact += 1
            continue
        t = next(i for i in range(new) if got[i] != want[i])
        if ref_logits is None:
            ref_logits = _reference_logits(
                params, cfg, max_prompt + max_new)
        logits = ref_logits(np.concatenate(
            [prompt, np.asarray(want[:t], np.int32)]))
        gap = float(logits.max() - min(logits[got[t]], logits[want[t]]))
        say(f"  request {rid} (prompt {len(prompt)}): token {t} differs "
            f"({got[t]} vs generate's {want[t]}); reference logit gap "
            f"to the best {gap:.4f} (tolerance {LOGIT_TIE_TOL})")
        if gap > LOGIT_TIE_TOL:
            raise AssertionError(
                f"request {rid}: serving and generate() disagree at "
                f"token {t} beyond a bf16 near-tie (gap {gap:.4f})")
        ties += 1
    say(f"  tokens equal generate() on {exact}/{requests} requests; {ties} "
        f"diverged at a bf16 near-tie, checked on logits")
    return {"exact": exact, "near_ties": ties}


# ------------------------------------------------------------------- looped

# Ouro-2.6B's widths (perfbench/configs/ouro_2p6b.json) at two layers: the
# stack runs four times over the same weights, every sub-layer's output is
# normed, and the cache holds 4 x 2 layers
LOOPED = {"hidden_size": 2048, "intermediate_size": 5632,
          "num_attention_heads": 16, "num_key_value_heads": 16,
          "head_dim": 128, "num_hidden_layers": 2, "vocab_size": 49152,
          "rms_norm_eps": 1e-6, "rope_theta": 1000000.0,
          "total_ut_steps": 4, "tie_word_embeddings": False,
          "torch_dtype": "bfloat16"}
LOOPED_MIX = ((40, 24), (100, 16), (64, 24), (128, 8))
# The widest gap by which a served token's float32 reference logit may lie
# below the reference's best: bf16 through 8 layer passes reads 0.02-0.06 at
# these widths (the 16-layer Mistral cells of PERF.md read the same), and a
# model with a pass or the output norms left out reads over 1.
LOOPED_GAP = 0.2


def phase_serve_looped(cfg_dict=None, mix=LOOPED_MIX, max_batch=4,
                       page_size=64, gap_limit=LOOPED_GAP) -> dict:
    """A stack run several times over shared weights, through the engine,
    against the plain reference's full forward: logits, not tokens."""
    from perfbench.references import ouro_looped as ref

    cfg_dict = dict(cfg_dict or LOOPED)
    cfg = llama.LlamaConfig(
        vocab_size=cfg_dict["vocab_size"], hidden_size=cfg_dict["hidden_size"],
        intermediate_size=cfg_dict["intermediate_size"],
        num_layers=cfg_dict["num_hidden_layers"],
        num_heads=cfg_dict["num_attention_heads"],
        num_kv_heads=cfg_dict["num_key_value_heads"], max_seq_len=4096,
        rope_theta=cfg_dict["rope_theta"], rms_eps=cfg_dict["rms_norm_eps"],
        dtype=jnp.dtype(cfg_dict["torch_dtype"]),
        num_passes=cfg_dict["total_ut_steps"], sandwich_norm=True)

    def describe(engine):
        cache = engine.scheduler.cache
        if cache.layers != cfg.num_passes * cfg.num_layers:
            raise AssertionError(f"the cache has {cache.layers} layers, not "
                                 f"{cfg.num_passes} x {cfg.num_layers}")
        say(f"  {cfg.num_layers} layers x {cfg.num_passes} passes: k_pages "
            f"{tuple(cache.k_pages.shape)} {cache.k_pages.dtype}; page "
            f"budget {engine.page_budget}")

    return _serve_against_reference(ref, cfg_dict, cfg, mix, max_batch,
                                    page_size, gap_limit, 2 ** 31 + 29, 64,
                                    describe)


def _serve_against_reference(ref, cfg_dict, cfg, mix, max_batch, page_size,
                             gap_limit, seed, pad_to, describe):
    """Seeded weights from the reference's ``init``, the ``mix`` of (prompt
    length, new tokens) through a ``ServingEngine``, and every served token's
    float32 reference logit against the reference's best at its position."""
    from perfbench.references.common import seed_words

    params = jax.jit(lambda lo, hi: ref.init(lo, hi, cfg_dict))(
        *seed_words(seed))
    max_prompt = max(p for p, _ in mix)
    max_new = max(n for _, n in mix)
    engine = ServingEngine(
        params, cfg, page_size=page_size, max_batch=max_batch, num_pages=None,
        max_prompt_len=max_prompt, max_new_cap=max_new)
    describe(engine)
    rng = np.random.default_rng(0)
    prompts = {}
    for p_len, new in mix:
        prompt = rng.integers(0, cfg.vocab_size, p_len, dtype=np.int32)
        prompts[engine.submit(prompt, new)] = (prompt, new)
    results = engine.run()      # raises if the decode step retraced

    length = -(-(max_prompt + max_new) // pad_to) * pad_to

    @jax.jit
    def gaps(params, tokens, rows, served):
        logits = ref.row_logits(params, tokens, rows, cfg_dict)
        return ref.served_gaps(logits, served)

    widest = 0.0
    for rid, (prompt, new) in sorted(prompts.items()):
        served = np.asarray(results[rid]["tokens"], np.int32)
        if len(served) != new:
            raise AssertionError(
                f"request {rid}: {len(served)} tokens, asked for {new}")
        seq = np.concatenate([prompt, served[:-1]])
        rows = np.minimum(len(prompt) - 1 + np.arange(max_new), len(seq) - 1)
        tokens = np.zeros(max_new, np.int32)
        tokens[:new] = served
        gap = float(np.asarray(gaps(
            params, np.pad(seq, (0, length - len(seq))), rows,
            tokens))[:new].max())
        say(f"  request {rid} (prompt {len(prompt)}, {new} tokens): widest "
            f"reference logit gap {gap:.4f} (limit {gap_limit})")
        widest = max(widest, gap)
    if not widest <= gap_limit:
        raise AssertionError(
            f"served tokens lie {widest:.4f} below the reference's best "
            f"logit, over the limit {gap_limit}")
    return {"widest_gap": widest}


# Five layers at Trinity-Large-Preview's widths (perfbench/configs/
# trinity_large_ep8_d5.json) with 8 of the 256 experts held and a window of
# 512: prompts of 1,024 put the windowed flash forward and a prefill cut by
# the window on the chip, which the benchmark's cell (window 4,096, prompts
# up to 4,096) never does; the short prompt decodes across the window's edge.
AFMOE = {"hidden_size": 3072, "intermediate_size": 12288,
         "moe_intermediate_size": 3072, "num_attention_heads": 48,
         "num_key_value_heads": 8, "head_dim": 128, "num_hidden_layers": 5,
         "num_dense_layers": 1, "layer_types": ["sliding_attention"] * 4
         + ["full_attention"], "sliding_window": 512, "num_experts": 8,
         "experts_held": {"first": 0, "count": 8, "of": 256},
         "num_experts_per_tok": 4, "num_shared_experts": 1,
         "score_func": "sigmoid", "route_norm": True, "route_scale": 2.448,
         "mup_enabled": True, "vocab_size": 25024, "rms_norm_eps": 1e-5,
         "rope_theta": 10000, "max_position_embeddings": 4096,
         "tie_word_embeddings": False, "torch_dtype": "bfloat16"}
AFMOE_MIX = ((1024, 24), (1024, 16), (480, 48), (768, 8))
# bf16 through five layers reads like the 16-layer Mistral cells; a token
# whose fourth and fifth experts lie a rounding apart is routed otherwise
# than the float32 reference routes it and moves a logit by an expert's
# whole contribution, so the limit is the benchmark cell's (PERF.md, "How
# correct is decided"); a model with the gate, the window or the shared
# expert left out reads several times over it (tests/run_models).
AFMOE_GAP = 0.5


def phase_serve_afmoe(cfg_dict=None, mix=AFMOE_MIX, max_batch=4,
                      page_size=128, gap_limit=AFMOE_GAP) -> dict:
    """A dense lead and dropless expert layers, sliding and full attention
    in one stack, through the engine, against the plain reference's full
    forward: logits, not tokens."""
    from perfbench.references import afmoe as ref
    from perfbench.runners import serve_afmoe

    cfg_dict = dict(cfg_dict or AFMOE)
    cfg = serve_afmoe.model_config(cfg_dict)

    def describe(engine):
        cache = engine.scheduler.cache
        say(f"  {cfg.num_dense_layers} dense + {cfg.expert_layers} expert "
            f"layers, experts {cfg.held} of {cfg.num_experts}, window "
            f"{cfg.sliding_window}: k_pages {tuple(cache.k_pages.shape)} "
            f"{cache.k_pages.dtype}; page budget {engine.page_budget}")

    return _serve_against_reference(ref, cfg_dict, cfg, mix, max_batch,
                                    page_size, gap_limit, 2 ** 31 + 33,
                                    ref.QUERY_BLOCK, describe)


# The cut of LFM2-24B-A2B as the benchmark serves it (its configuration file,
# every width and all 64 experts a layer), a small batch: prompts that end 24
# and 36 short of their buckets (the conv state is taken at the true length
# under padding), one on a page's edge and one shorter than the convolution.
LFM2_MIX = ((1000, 16), (1500, 24), (640, 8), (2, 12))
# bf16 through nine layers, all experts held: a token whose fourth and fifth
# expert scores lie a rounding apart is routed otherwise than the float32
# reference routes it and moves a logit by an expert's whole contribution, in
# every one of eight layers. These 60 tokens read 0.13-0.48 (my chip run, PR
# 35); the benchmark's samples of 700 read up to 1.94 on sound runs, and a
# token drawn at random 4.3 in the mean (PERF.md, How correct is decided): the
# limit stands between, no limit on precision. A conv state taken at the
# bucket's end, B and C swapped or the taps reversed read like a wrong token
# (tests/run_serving, float32: over 0.01 against 0).
LFM2_GAP = 2.5


def phase_serve_lfm2(cfg_dict=None, mix=LFM2_MIX, max_batch=4,
                     page_size=128, gap_limit=LFM2_GAP) -> dict:
    """Gated short-convolution layers beside attention layers, a dense lead
    and dropless experts all held, through the engine, its pages and its
    state buffer, against the plain reference's full forward: logits, not
    tokens."""
    from perfbench.references import lfm2_moe as ref
    from perfbench.runners import serve_lfm2

    if cfg_dict is None:
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "perfbench", "configs",
                               "lfm2_24b_a2b_d9.json")) as f:
            cfg_dict = json.load(f)
    cfg = serve_lfm2.model_config(cfg_dict)

    def describe(engine):
        cache = engine.scheduler.cache
        if (cache.layers, cache.conv_state.shape[0]) != (
                cfg.cache_layers, cfg.conv_layers):
            raise AssertionError(
                f"the cache has {cache.layers} layers of pages and "
                f"{cache.conv_state.shape[0]} of state, not "
                f"{cfg.cache_layers} and {cfg.conv_layers}")
        say(f"  {cfg.conv_layers} conv + {cfg.cache_layers} attention "
            f"layers, {cfg.expert_layers} of them with {cfg.num_experts} "
            f"experts: k_pages {tuple(cache.k_pages.shape)}, conv_state "
            f"{tuple(cache.conv_state.shape)} {cache.k_pages.dtype}; page "
            f"budget {engine.page_budget}")

    return _serve_against_reference(ref, cfg_dict, cfg, mix, max_batch,
                                    page_size, gap_limit, 2 ** 31 + 35,
                                    ref.QUERY_BLOCK, describe)


# --------------------------------------------------------------------- mesh


class _DpTpSync:
    """examples/gpt2_train.py's scheme on a ('dp', 'tp') mesh: every
    parameter is made varying over both axes, so every gradient arrives
    per-device and is averaged by hand — over dp always, over tp for the
    parameters tp does not shard."""

    def __init__(self, specs):
        self.specs = specs

    @staticmethod
    def prepare(params):
        for axis in ("dp", "tp"):
            params = jax.tree_util.tree_map(
                lambda a, axis=axis: make_varying(a, axis), params)
        return params

    def reduce(self, grads, loss):
        pmean = lambda t, axis: jax.lax.pmean(make_varying(t, axis), axis)
        grads = jax.tree_util.tree_map(lambda g: pmean(g, "dp"), grads)
        grads = jax.tree_util.tree_map(
            lambda g, s: g if "tp" in s else pmean(g, "tp"), grads,
            self.specs)
        return grads, pmean(pmean(loss, "dp"), "tp")


class _DdpSync:
    """Replicated parameters over a ('dp',) mesh: autodiff and the fused
    kernels' VJP rules hand back summed gradients, and
    ``sync_autodiff_gradients`` turns them into the global-batch mean."""

    @staticmethod
    def prepare(params):
        return params

    @staticmethod
    def reduce(grads, loss):
        return (sync_autodiff_gradients(grads, axis_name="dp"),
                jax.lax.pmean(loss, "dp"))


def _mesh_step_loss(mesh, cfg, batch, param_specs, reduction, tp_axis,
                    overflow_axes):
    """Build the state SHARDED over ``mesh`` (never whole on device 0),
    take one step under shard_map, and return the loss after checking the
    parameters' placement and every device's memory."""
    handle, tx, init_state, data = _gpt2_setup(cfg, batch)
    shapes = jax.eval_shape(init_state)
    specs = (param_specs, opt_partition_specs(tx, shapes[0], param_specs),
             jax.tree_util.tree_map(lambda _: P(), shapes[2]))
    named = lambda tree: jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P))
    state = jax.jit(init_state, out_shardings=named(specs))()
    data_spec = (P("dp", None), P("dp", None))
    data = jax.device_put(data, named(data_spec))
    step = jax.jit(shard_map(
        _gpt2_step(handle, tx, cfg, tp_axis=tp_axis, reduction=reduction,
                   overflow_axes=overflow_axes),
        mesh=mesh, in_specs=(*specs, data_spec), out_specs=(*specs, P())),
        donate_argnums=(0, 1, 2))
    *state, loss = step(*state, data)
    loss = float(sync(loss))
    for path, leaf in jax.tree_util.tree_leaves_with_path(state[0]):
        if leaf.sharding.device_set != set(mesh.devices.flat):
            raise AssertionError(
                f"parameter {jax.tree_util.keystr(path)} lives on "
                f"{len(leaf.sharding.device_set)} of {mesh.size} devices")
    in_use = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in mesh.devices.flat]
    say(f"    bytes_in_use per device: {in_use}")
    if None not in in_use:       # the CPU reports no allocator stats
        param_bytes = sum(l.nbytes for l in
                          jax.tree_util.tree_leaves(state[0]))
        if min(in_use) < param_bytes // (4 * mesh.size):
            raise AssertionError(
                f"a device holds almost nothing: {in_use} (parameters "
                f"total {param_bytes} B over {mesh.size} devices)")
    return loss


def phase_mesh(reference_loss, cfg=None, batch=8, rtol=2e-2) -> dict:
    """The GPT-2 step on four chips: dp=2 x tp=2 as examples/gpt2_train.py
    builds it, and dp=4 DDP. Each first loss must equal the one-chip loss
    for the same global batch within bf16 tolerance."""
    cfg = cfg or gpt2.gpt2_345m()
    devices = np.array(jax.devices()[:4])
    tp_specs = gpt2.param_specs(cfg)
    replicated = jax.tree_util.tree_map(
        lambda _: P(), tp_specs, is_leaf=lambda x: isinstance(x, P))
    losses = {}
    for name, mesh, specs, reduction, tp_axis, axes in (
            ("dp2_tp2", Mesh(devices.reshape(2, 2), ("dp", "tp")), tp_specs,
             _DpTpSync(tp_specs), "tp", ("dp", "tp")),
            ("ddp4", Mesh(devices, ("dp",)), replicated, _DdpSync(), None,
             ())):
        say(f"  {name}:")
        loss = _mesh_step_loss(mesh, cfg, batch, specs, reduction, tp_axis,
                               axes)
        say(f"    loss {loss:.4f} (one chip {reference_loss:.4f})")
        if abs(loss - reference_loss) > rtol * abs(reference_loss):
            raise AssertionError(
                f"{name} loss {loss:.4f} != one-chip loss "
                f"{reference_loss:.4f} (rtol {rtol})")
        losses[name] = loss
    return losses


# --------------------------------------------------------------------- main


def _count_cache_events(counts):
    def on_event(name, **_kw):
        if name.startswith("/jax/compilation_cache/cache_"):
            counts[name.rsplit("/", 1)[-1]] += 1

    jax.monitoring.register_event_listener(on_event)


def main() -> int:
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: no TPU — jax selected the {backend!r} backend; "
              f"nothing was run", file=sys.stderr)
        return EXIT_REFUSED

    from apex_tpu.runtime import runtime_available
    from apex_tpu.runtime.compile_cache import enable_compile_cache
    from apex_tpu.tuning import cache as tuning_cache

    cache_dir = enable_compile_cache()
    cache_events = {"cache_hits": 0, "cache_misses": 0}
    _count_cache_events(cache_events)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    versions = {p: importlib.metadata.version(p)
                for p in ("jax", "jaxlib", "libtpu")}
    say(f"chip_smoke: {device['count']} x {device['kind']} "
        f"({device['platform']}); {versions}")
    say(f"compile cache: {cache_dir}")
    say("host runtime: " + (
        "native (csrc/libapex_tpu_host.so)" if runtime_available()
        else "numpy fallback (csrc/ did not build or load)"))
    if os.path.exists(tuning_cache.cache_path()):
        # a tuning cache changes tiles and Pallas-vs-XLA verdicts; this
        # run proves the defaults
        print(f"chip_smoke: tuning cache {tuning_cache.cache_path()} "
              f"present — move it away, the smoke runs with none",
              file=sys.stderr)
        return EXIT_REFUSED
    say("tuning cache: none (default tiles and kernel verdicts)")

    t_start = time.perf_counter()

    def run(name, phase, *args):
        say(f"[{name}]")
        t0 = time.perf_counter()
        out = phase(*args)
        say(f"[{name}] ok in {time.perf_counter() - t0:.0f}s")
        jax.clear_caches()      # drop executables pinning donated buffers
        return out

    run("kernels", phase_kernels)
    train = run("train", phase_train)
    run("serve", phase_serve)
    run("looped", phase_serve_looped)
    run("afmoe", phase_serve_afmoe)
    run("lfm2", phase_serve_lfm2)
    if device["count"] >= 4:
        run("mesh", phase_mesh, train["first_loss"])
    else:
        say(f"[mesh] skipped: {device['count']} device(s) visible, the "
            f"dp=2 x tp=2 and dp=4 meshes need 4")
    say(f"all phases ok in {time.perf_counter() - t_start:.0f}s; compile "
        f"cache {cache_events['cache_hits']} hits, "
        f"{cache_events['cache_misses']} misses")
    # exactly these keys: the driver parses this line
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
