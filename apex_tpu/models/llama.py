"""Llama model family (flagship) — TP/SP/CP/PP-composable functional model.

Role in the framework: the reference (NVIDIA Apex) ships no model zoo, but
its headline benchmarks run Megatron-style transformers built from its
primitives (ColumnParallelLinear/RowParallelLinear, FusedRMSNorm, fused
softmax/RoPE — ref apex/transformer/tensor_parallel/layers.py,
apex/normalization/fused_layer_norm.py, apex/transformer/functional/).
This module is the TPU-native assembly of those same primitives into the
Llama-3 architecture (RMSNorm pre-norm, SwiGLU, GQA, RoPE).

Design: pure-functional param pytrees with stacked per-layer weights
([L, ...] leading dim, consumed by ``lax.scan``) so the whole depth compiles
as one rolled loop (fast compile, remat-friendly). Every collective degrades
to a no-op when its mesh axis is unbound, so the SAME code runs single-chip,
under tp-only shard_map, and as one pipeline stage:

- tp:   column/row-parallel projections, vocab-parallel embedding + CE
- sp:   ``sequence_parallel=True`` switches tp collectives to
        reduce_scatter/all_gather over the sequence dim
- cp:   ring attention over the 'cp' axis; RoPE uses global positions
- pp:   :func:`stage_fn` applies a contiguous slice of layers — feed it to
        ``pipeline_parallel.schedules``
- ep:   ``num_experts > 0`` swaps the dense SwiGLU MLP for Mixtral-style
        top-k routed experts (apex_tpu.transformer.moe); experts shard
        over the 'ep' axis, the router replicates. The load-balancing aux
        loss is returned by :func:`loss_fn`; the pipeline ``stage_fn``
        path drops it (documented — activations are the only pp payload).

The layer itself is written once, in :func:`block`. Training
(:func:`decoder_layer`), ``models/generate.py`` and the two serving programs
(``serving/scheduler.py``) call it and pass in what differs between them:
how a weight multiplies, where K and V live, and which FFN runs.

Looped stacks (Ouro): ``num_passes > 1`` applies the SAME [L, ...] stack
that many times (:func:`scan_passes`), the final norm between passes, and
``sandwich_norm`` norms each sub-layer's output as well as its input.
Pass ``t`` of layer ``l`` has keys and values of its own, so anything that
caches them holds ``cfg.cache_layers = num_passes * num_layers`` layers,
pass-major. The exit gate's weights are in the tree; the served token is
the last pass's (the published ``early_exit_threshold`` of 1), and
:func:`exit_distribution` gives the gate's reading of every pass.

Stacks of more than one layer kind (the ``afmoe`` form, Trinity). Two
things may differ from layer to layer, and both are facts of the published
model that ``LlamaConfig`` states. *The FFN:* the first ``num_dense_layers``
are dense SwiGLU, the others dropless expert layers
(``moe_capacity_factor=None``: :func:`moe_ffn`, sigmoid or softmax routing
over all ``num_experts``, the experts of ``experts_held`` computed here, a
shared expert beside them). Their weights differ in shape, so they are two
stacks, ``params["dense_layers"]`` and ``params["layers"]``, each scanned
(:func:`stacks`, :func:`scan_passes`). *The attention:* ``layer_types``
names each layer ``sliding_attention`` (keys ``i - sliding_window < j <=
i``) or ``full_attention``; ``rope_full_attention=False`` leaves the full
layers without a position signal. Sliding and full layers have the same
weights, so a layer's kind is data of its scan step (``lp["sliding"]``, a
bool beside the weights): a window bound that is ``pos - window + 1`` or 0,
a select between the rotated and the plain heads. ``qk_norm`` (RMSNorm on
each query and key head), ``attn_output_gate`` (a sigmoid gate on the
heads' output) and ``embed_scale`` are written once, in :func:`attention`
and :func:`embed`. With the defaults none of this is in any program.

Stacks of two weight shapes (the ``lfm2`` form). *The operator:*
``layer_types`` may name a layer ``conv``: a gated short convolution
(:func:`short_conv`: an input projection to three parts, a depthwise causal
convolution of ``conv_L_cache`` taps on the product of two of them, gated by
the third, an output projection) where the others have attention. It keeps
no K and V (``cfg.cache_layers`` counts the attention layers) and
``conv_L_cache - 1`` positions of state a row (``cfg.conv_layers``).
:func:`block`'s first half is "the operator on the normed stream", and the
caller's ``attend`` says what it keeps, for either kind. A conv layer's
weights have other shapes than an attention layer's, so such a model
(``cfg.hybrid``) is kept and scanned run by run: ``params["runs"]`` holds one
stack a run of consecutive layers of one shape (``cfg.runs``), none carrying
another shape's weights, ``params["experts"]`` the routed experts of all its
expert layers in one stack, and :func:`scan_passes` gives each run its own
slice of what its operator's layers own.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.models._common import fan_in_normal

from apex_tpu.normalization.fused_layer_norm import fused_rms_norm_affine
from apex_tpu.transformer.context_parallel import (
    context_parallel_positions,
    ring_attention,
)
from apex_tpu.ops.flash_attention import flash_attention
from apex_tpu.transformer.functional.rope import apply_rotary_qk
from apex_tpu.transformer.tensor_parallel.cross_entropy import (
    vocab_parallel_cross_entropy,
)
from apex_tpu.transformer.tensor_parallel.layers import (
    column_parallel_linear,
    row_parallel_linear,
    vocab_parallel_embedding,
)
from apex_tpu.transformer.tensor_parallel.mappings import (
    _axis_bound,
    gather_from_sequence_parallel_region,
    scatter_to_sequence_parallel_region,
)


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    rms_eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16
    tie_embeddings: bool = False
    # Mixtral-style MoE: 0 = dense SwiGLU; >0 routes tokens through that
    # many SwiGLU experts (top-k, capacity-dropped) over the 'ep' axis
    num_experts: int = 0
    moe_top_k: int = 2
    # None: token choice with no capacity, no token ever dropped (the form
    # that is served: transformer/moe.dropless_experts)
    moe_capacity_factor: Optional[float] = 1.25
    # looped stack: the [L, ...] layers run this many times over the same
    # weights, the final norm between passes (Ouro's ``total_ut_steps``)
    num_passes: int = 1
    # RMSNorm on each sub-layer's output too, before the residual add
    sandwich_norm: bool = False
    # a head's width where it is not hidden_size / num_heads
    attn_head_dim: Optional[int] = None
    # each layer's operator: "sliding_attention" or "full_attention", or
    # "conv" (a gated short convolution, no K and V); () is full attention
    # everywhere. A sliding layer's query i reads keys
    # i - sliding_window < j <= i
    layer_types: Tuple[str, ...] = ()
    sliding_window: Optional[int] = None
    # False: only the sliding layers rotate; a full layer has no position
    # signal
    rope_full_attention: bool = True
    # RMSNorm with a gain over each query and key head, before the rotation
    qk_norm: bool = False
    # o = (heads * sigmoid(h @ wgate)) @ wo
    attn_output_gate: bool = False
    # the embedding's output is multiplied by this (muP: sqrt(hidden))
    embed_scale: float = 1.0
    # a dropless expert model: the first layers are dense SwiGLU of
    # intermediate_size, the others route over num_experts experts of
    # moe_intermediate_size beside num_shared_experts that every token uses
    num_dense_layers: int = 0
    moe_intermediate_size: Optional[int] = None
    num_shared_experts: int = 0
    router_score: str = "softmax"            # or "sigmoid"
    route_norm: bool = True
    route_scale: float = 1.0
    # a [num_experts] bias that enters the selection, not the weight
    router_bias: bool = False
    # (first, count): the experts whose weights are here, of num_experts
    # routed over (one chip's share of an expert-parallel deployment);
    # None holds them all
    experts_held: Optional[Tuple[int, int]] = None
    # what route_norm adds to the sum it divides a token's weights by
    route_norm_eps: float = 1e-20
    # a "conv" layer: the taps of its depthwise causal convolution (it keeps
    # the conv_L_cache - 1 positions before the newest); a bias a channel is
    # a key the published configurations carry, and none sets it
    conv_L_cache: int = 3
    conv_bias: bool = False

    def __post_init__(self):
        if self.layer_types and len(self.layer_types) != self.num_layers:
            raise ValueError(f"{len(self.layer_types)} layer_types for "
                             f"{self.num_layers} layers")
        if set(self.layer_types) - {"sliding_attention", "full_attention",
                                    "conv"}:
            raise ValueError(f"unknown layer type in {self.layer_types}")
        if self.conv_bias:
            raise ValueError("conv_bias: no configuration or reference "
                             "states a conv layer with a bias")
        if self.hybrid and self.moe and not self.dropless:
            raise ValueError("conv layers beside capacity-dropped experts: "
                             "a stack of two layer shapes has dense or "
                             "dropless expert FFNs")
        if self.hybrid and self.num_passes > 1:
            raise NotImplementedError(
                "a looped stack with conv layers: no model has one")
        if self.windowed and not self.sliding_window:
            raise ValueError("sliding_attention layers need a "
                             "sliding_window")
        if not self.dropless and (self.num_dense_layers
                                  or self.num_shared_experts
                                  or self.experts_held):
            raise ValueError(
                "num_dense_layers, num_shared_experts and experts_held "
                "describe a dropless expert model (num_experts > 0, "
                "moe_capacity_factor None)")
        if self.dropless and self.num_passes > 1:
            raise NotImplementedError(
                "a looped stack of expert layers: no model has one")

    @property
    def moe(self) -> bool:
        return self.num_experts > 0

    @property
    def dropless(self) -> bool:
        """Expert layers with no capacity: no token is ever dropped."""
        return self.moe and self.moe_capacity_factor is None

    @property
    def windowed(self) -> bool:
        """Whether any layer is a sliding one."""
        return "sliding_attention" in self.layer_types

    @property
    def held(self) -> Tuple[int, int]:
        """``(first, count)`` of the experts whose weights are here."""
        return self.experts_held or (0, self.num_experts)

    @property
    def expert_layers(self) -> int:
        return self.num_layers - self.num_dense_layers if self.moe else 0

    @property
    def hybrid(self) -> bool:
        """Whether some layers are gated short convolutions: a stack of two
        weight shapes, kept and scanned run by run (:attr:`runs`)."""
        return "conv" in self.layer_types

    @property
    def conv_layers(self) -> int:
        """Layers that keep conv state (``[conv_L_cache - 1, hidden]`` a
        row) and no K and V."""
        return self.layer_types.count("conv")

    @property
    def cache_layers(self) -> int:
        """Layers of K and V behind ``num_layers`` layers of weights, of
        which the conv layers have none: pass ``t`` of attention layer ``l``
        is cache layer ``t * (num_layers - conv_layers) + l``."""
        return self.num_passes * (self.num_layers - self.conv_layers)

    @property
    def runs(self) -> Tuple[Tuple[str, bool, int], ...]:
        """The stack as runs of consecutive layers of one weight shape, in
        order: ``(operator, routed, layers)``, ``operator`` ``"conv"`` or
        ``"attention"`` (sliding and full layers share a shape), ``routed``
        whether the FFN is an expert layer."""
        out = []
        for at in range(self.num_layers):
            kind = ("conv" if self.layer_types[at:at + 1] == ("conv",)
                    else "attention",
                    self.moe and at >= self.num_dense_layers)
            if out and out[-1][0] == kind:
                out[-1][1] += 1
            else:
                out.append([kind, 1])
        return tuple(kind + (n,) for kind, n in out)

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim or self.hidden_size // self.num_heads


def llama3_8b(**over) -> LlamaConfig:
    return LlamaConfig(**over)


def flagship_0p9b(**over) -> LlamaConfig:
    """A 0.9B llama that fits one chip with room for a cache: the model
    ``chip_smoke.py``'s serve check drives through ``ServingEngine`` against
    ``generate`` (``bench.py`` and ``tools/tpu_profile.py`` use it too)."""
    kw = dict(vocab_size=32000, hidden_size=2048, intermediate_size=5632,
              num_layers=8, num_heads=16, num_kv_heads=8, max_seq_len=2048,
              dtype=jnp.bfloat16)
    kw.update(over)
    return LlamaConfig(**kw)


def tiny(**over) -> LlamaConfig:
    """Test-scale config (tp/cp-divisible heads)."""
    kw = dict(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, max_seq_len=128, dtype=jnp.float32,
    )
    kw.update(over)
    return LlamaConfig(**kw)


def init_params(key, cfg: LlamaConfig):
    """Full (unsharded) parameter pytree; layer weights stacked on dim 0.

    Shard for tp with ``P(None, 'tp')`` on column kernels (wq/wk/wv/wg/wu),
    ``P(None, 'tp', None)`` on row kernels' input dim (wo/wd), ``P('tp',)``
    on the embedding's vocab dim and the lm head's output dim.
    """
    h, i, d = cfg.hidden_size, cfg.intermediate_size, cfg.head_dim
    nq, nkv, L = cfg.num_heads, cfg.num_kv_heads, cfg.num_layers
    dt = cfg.dtype

    ks = jax.random.split(key, 10)

    def norm(k, *shape, fan_in=None):
        return fan_in_normal(k, *shape, fan_in=fan_in, dtype=dt)

    if cfg.dropless or cfg.hybrid:
        return _init_dropless(key, cfg)
    layers = {
        "attn_norm": jnp.ones((L, h), dt),
        "wq": norm(ks[1], L, h, nq * d),
        "wk": norm(ks[2], L, h, nkv * d),
        "wv": norm(ks[3], L, h, nkv * d),
        "wo": norm(ks[4], L, nq * d, h),
        "mlp_norm": jnp.ones((L, h), dt),
    }
    if cfg.sandwich_norm:
        layers["attn_post_norm"] = jnp.ones((L, h), dt)
        layers["mlp_post_norm"] = jnp.ones((L, h), dt)
    if cfg.qk_norm:
        layers["q_norm"] = jnp.ones((L, d), dt)
        layers["k_norm"] = jnp.ones((L, d), dt)
    if cfg.attn_output_gate:
        layers["wgate"] = norm(jax.random.fold_in(key, 11), L, h, nq * d)
    if cfg.moe:
        E = cfg.num_experts
        layers.update({
            "router": (jax.random.normal(ks[9], (L, h, E)) * 0.02
                       ).astype(dt),
            "wg": norm(ks[5], L, E, h, i),
            "wu": norm(ks[6], L, E, h, i),
            "wd": norm(ks[7], L, E, i, h),
        })
    else:
        layers.update({
            "wg": norm(ks[5], L, h, i),
            "wu": norm(ks[6], L, h, i),
            "wd": norm(ks[7], L, i, h),
        })
    params = {
        "embed": norm(ks[0], cfg.vocab_size, h, fan_in=h),
        "layers": layers,
        "final_norm": jnp.ones((h,), dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = norm(ks[8], h, cfg.vocab_size, fan_in=h)
    if cfg.num_passes > 1:
        # Linear(h, 1): one exit logit a position after every pass
        params["exit_gate"] = {
            "w": norm(jax.random.fold_in(key, 10), h, 1, fan_in=h),
            "b": jnp.zeros((1,), dt)}
    return params


EXPERT_WEIGHTS = ("wg", "wu", "wd")


def _experts_init(keys, n, cfg: LlamaConfig):
    """The routed experts of ``n`` expert layers, ``[n, held, ...]`` a
    weight (``EXPERT_WEIGHTS``), one key each."""
    h, held = cfg.hidden_size, cfg.held[1]
    f = cfg.moe_intermediate_size or cfg.intermediate_size
    return {name: fan_in_normal(k, *shape, dtype=cfg.dtype)
            for name, k, shape in zip(EXPERT_WEIGHTS, keys, (
                (n, held, h, f), (n, held, h, f), (n, held, f, h)))}


def _stack_init(key, n, cfg: LlamaConfig, operator: str, routed: bool,
                experts: bool = True):
    """``n`` layers of one weight shape, stacked on dim 0: the operator's
    weights (attention's, or a gated short convolution's: ``conv_in`` ``[h,
    3h]`` whose thirds are B, C and X, the taps ``conv_w`` ``[h,
    conv_L_cache]``, ``conv_out``), the norms, and the FFN's (dense SwiGLU,
    or the router and, with ``experts``, those of ``cfg.experts_held``)."""
    h, d, dt = cfg.hidden_size, cfg.head_dim, cfg.dtype
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    f = cfg.moe_intermediate_size or cfg.intermediate_size

    def norm(k, *shape, fan_in=None):
        return fan_in_normal(k, *shape, fan_in=fan_in, dtype=dt)

    ks = jax.random.split(key, 6)
    out = {"attn_norm": jnp.ones((n, h), dt),
           "mlp_norm": jnp.ones((n, h), dt)}
    if operator == "conv":
        taps = cfg.conv_L_cache
        out.update(conv_in=norm(ks[0], n, h, 3 * h),
                   conv_w=norm(ks[1], n, h, taps, fan_in=taps),
                   conv_out=norm(ks[2], n, h, h))
    else:
        out.update(wq=norm(ks[0], n, h, nq * d), wk=norm(ks[1], n, h, nkv * d),
                   wv=norm(ks[2], n, h, nkv * d), wo=norm(ks[3], n, nq * d, h))
        if cfg.qk_norm:
            out["q_norm"] = jnp.ones((n, d), dt)
            out["k_norm"] = jnp.ones((n, d), dt)
        if cfg.attn_output_gate:
            out["wgate"] = norm(ks[4], n, h, nq * d)
    if cfg.sandwich_norm:
        out["attn_post_norm"] = jnp.ones((n, h), dt)
        out["mlp_post_norm"] = jnp.ones((n, h), dt)
    if not routed:
        k, i = jax.random.split(ks[5], 3), cfg.intermediate_size
        out.update(wg=norm(k[0], n, h, i), wu=norm(k[1], n, h, i),
                   wd=norm(k[2], n, i, h))
        return out
    k = jax.random.split(ks[5], 8)
    fs = f * cfg.num_shared_experts
    out["router"] = (jax.random.normal(k[0], (n, h, cfg.num_experts))
                     * 0.02).astype(dt)
    if experts:
        out.update(_experts_init(k[1:4], n, cfg))
    if cfg.router_bias:
        out["router_bias"] = jnp.zeros((n, cfg.num_experts), jnp.float32)
    if fs:
        out.update(shared_wg=norm(k[4], n, h, fs),
                   shared_wu=norm(k[5], n, h, fs),
                   shared_wd=norm(k[6], n, fs, h))
    return out


def _init_dropless(key, cfg: LlamaConfig):
    """:func:`init_params` of a dropless expert model, or of one with conv
    layers. Where every layer is an attention layer: the dense lead in
    ``dense_layers`` (absent without one), the expert layers in ``layers``,
    each stacked on dim 0. Where some are conv layers: ``runs``, a tuple of
    such stacks, one a run of ``cfg.runs``, none holding another shape's
    weights; the routed experts of all its expert layers lie in one stack of
    their own, ``experts`` (``[expert layers, held, ...]`` a weight), which
    no scan slices (:func:`expert_stack`). The expert weights are those of
    ``cfg.experts_held`` alone, the router's all ``num_experts`` wide."""
    h, dt = cfg.hidden_size, cfg.dtype
    ks = jax.random.split(key, 4)
    params = {"embed": fan_in_normal(ks[0], cfg.vocab_size, h, fan_in=h,
                                     dtype=dt),
              "final_norm": jnp.ones((h,), dt)}
    if cfg.hybrid:
        params["runs"] = tuple(
            _stack_init(k, n, cfg, operator, routed, experts=False)
            for k, (operator, routed, n) in zip(
                jax.random.split(ks[1], len(cfg.runs)), cfg.runs))
        if cfg.moe:
            params["experts"] = _experts_init(
                jax.random.split(ks[2], 3), cfg.expert_layers, cfg)
    else:
        params["layers"] = _stack_init(ks[1], cfg.expert_layers, cfg,
                                       "attention", True)
        if cfg.num_dense_layers:
            params["dense_layers"] = _stack_init(
                ks[2], cfg.num_dense_layers, cfg, "attention", False)
    if not cfg.tie_embeddings:
        params["lm_head"] = fan_in_normal(ks[3], h, cfg.vocab_size, fan_in=h,
                                          dtype=dt)
    return params


def _rmsnorm(x, w, eps):
    return fused_rms_norm_affine(x, w, (x.shape[-1],), eps=eps)


def post_norm(y, lp, name, cfg: LlamaConfig):
    """A sub-layer's output on its way to the residual add: normed by
    ``lp[name]`` under ``sandwich_norm``, untouched otherwise."""
    return _rmsnorm(y, lp[name], cfg.rms_eps) if cfg.sandwich_norm else y


def products(x, lp, *names):
    """``x`` times each named weight of the layer, in ``x``'s dtype: the
    plain form of :func:`block`'s product hook."""
    return (jnp.matmul(x, lp[n].astype(x.dtype)) for n in names)


def _rotate(q, k, lp, cfg: LlamaConfig, positions):
    """RoPE on the heads; where only the sliding layers rotate, a select on
    the layer's kind (and no rotation at all where no layer is a sliding
    one)."""
    if not (cfg.rope_full_attention or cfg.windowed):
        return q, k
    rq, rk = apply_rotary_qk(q, k, positions=positions, base=cfg.rope_theta)
    if cfg.rope_full_attention:
        return rq, rk
    return (jnp.where(lp["sliding"], rq, q), jnp.where(lp["sliding"], rk, k))


def sliding_start(cfg: LlamaConfig, pos):
    """The first position a sliding layer's query at ``pos`` reads."""
    return jnp.maximum(pos - cfg.sliding_window + 1, 0)


def window_start(lp, cfg: LlamaConfig, pos):
    """The first position a query at ``pos`` reads in this layer:
    :func:`sliding_start` on a sliding layer, 0 on a full one; None where
    the model has no sliding layer."""
    if not cfg.windowed:
        return None
    return jnp.where(lp["sliding"], sliding_start(cfg, pos), 0)


def causal_attention(q, k, v, lp, cfg: LlamaConfig):
    """Causal flash attention of a whole sequence on this layer's kind:
    within the window on a sliding layer. A sequence no longer than the
    window is cut by none, and every layer runs the plain call."""
    scale = cfg.head_dim ** -0.5
    if not cfg.windowed or q.shape[1] <= cfg.sliding_window:
        return flash_attention(q, k, v, causal=True, scale=scale)
    return jax.lax.cond(
        lp["sliding"],
        lambda: flash_attention(q, k, v, causal=True, scale=scale,
                                window=cfg.sliding_window),
        lambda: flash_attention(q, k, v, causal=True, scale=scale))


def conv_window(cfg: LlamaConfig, state=None, length=None):
    """A conv layer's ``attend`` (:func:`block`) for a whole sequence or a
    step from a row's state: ``window(u) -> (full, kept)``. ``full`` is ``u``
    ``[b, s, h]`` behind the ``conv_L_cache - 1`` positions before it:
    ``state`` ``[b, conv_L_cache - 1, h]``, or zeros at a sequence's start.
    ``kept`` is the state a step at position ``length`` starts from (``u`` at
    ``length - conv_L_cache + 1 .. length - 1``, zeros before position 0);
    without ``length``, at the end of ``u``. A prompt padded to a bucket gives
    its true length: the state is not the bucket's end's."""
    past = cfg.conv_L_cache - 1

    def window(u):
        before = (jnp.zeros((u.shape[0], past, u.shape[2]), u.dtype)
                  if state is None else state.astype(u.dtype))
        full = jnp.concatenate([before, u], axis=1)
        at = u.shape[1] if length is None else length
        return full, jax.lax.dynamic_slice_in_dim(full, at, past, axis=1)

    return window


def short_conv(h, lp, cfg: LlamaConfig, window, mm=products):
    """The gated short convolution on the normed stream ``h [b, s, h]``:
    ``[B | C | X] = h W_in``; ``u = B * X``; ``c_t = sum_j w[:, j] *
    u_{t - (K - 1) + j}`` (depthwise, causal, ``K = conv_L_cache`` taps, in
    float32); ``y = (C * c) W_out``. ``window(u) -> (full, kept)`` is the
    caller's (:func:`conv_window`): it puts ``u`` behind the ``K - 1``
    positions before it and says what state is kept, as ``attend`` does for K
    and V. One wording for a whole sequence and for one position from a
    state. Returns ``(y, kept)``."""
    with jax.named_scope("llama/short_conv"):
        (bcx,) = mm(h, lp, "conv_in")
        gate_in, gate_out, x = jnp.split(bcx, 3, axis=-1)
        full, kept = window(gate_in * x)
        s = h.shape[1]
        taps = lp["conv_w"].astype(jnp.float32)
        c = sum(taps[:, j] * full[:, j:j + s].astype(jnp.float32)
                for j in range(cfg.conv_L_cache))
        (y,) = mm(gate_out * c.astype(h.dtype), lp, "conv_out")
        return y, kept


def attention(h, lp, cfg: LlamaConfig, positions, attend, mm=products):
    """Grouped-query attention on the normed stream: the projections, the
    norm on each query and key head (``qk_norm``), the rotation, which a
    layer of a kind that does not rotate passes by (:func:`_rotate`),
    ``attend`` on the rotated heads, the sigmoid gate on the heads' output
    (``attn_output_gate``) and the output projection. The window of a
    sliding layer is ``attend``'s: it alone knows the keys. Returns ``(y,
    kept)``."""
    d = cfg.head_dim
    with jax.named_scope("llama/attention"):
        q, k, v, *gate = (
            y.reshape(*y.shape[:2], -1, d) for y in mm(
                h, lp, "wq", "wk", "wv",
                *(("wgate",) if cfg.attn_output_gate else ())))
        if cfg.qk_norm:
            q = _rmsnorm(q, lp["q_norm"], cfg.rms_eps)
            k = _rmsnorm(k, lp["k_norm"], cfg.rms_eps)
        q, k = _rotate(q, k, lp, cfg, positions)
        o, kept = attend(q, k, v)
        o = o.reshape(*o.shape[:2], -1)
        if gate:
            o = o * jax.nn.sigmoid(gate[0].reshape(o.shape))
        (y,) = mm(o, lp, "wo")
        return y, kept


def block(x, lp, cfg: LlamaConfig, positions, attend, mm=products, ffn=None):
    """The Llama decoder block on one layer's (unstacked) weights ``lp``:
    the one place the sub-layer sequence is written: the operator on the
    normed stream, then the FFN on the normed stream, each added to the
    residual. Training, ``generate`` and both serving programs call it and
    pass in what differs:

    - ``attend``, what the operator keeps and where. On an attention layer
      ``attend(q, k, v) -> (o, kept)``: attention on the rotated heads
      (``q`` ``[b, s, nq, d]``, ``k`` and ``v`` ``[b, s, nkv, d]``) and where
      K and V live: nowhere (training), returned whole (the prefills), put
      into a cache that is then attended to (the decode steps). On a conv
      layer (``lp`` has ``conv_in``: :func:`short_conv`) ``attend(u) ->
      (full, kept)``: the positions before ``u`` and the state kept
      (:func:`conv_window`). ``kept`` is handed back beside the stream,
      untouched.
    - ``mm(x, lp, *names)``: ``x`` times each named weight, an iterable of
      one product a name. The names of one call share their input, so a
      hook that has to gather it (sequence parallelism) does so once a
      half-block.
    - ``ffn(h, lp)``: the feed-forward on the normed stream; without one,
      the dense SwiGLU through ``mm``.

    Returns ``(x, kept)``."""
    h = _rmsnorm(x, lp["attn_norm"], cfg.rms_eps)
    if "conv_in" in lp:
        y, kept = short_conv(h, lp, cfg, attend, mm)
    else:
        y, kept = attention(h, lp, cfg, positions, attend, mm)
    x = x + post_norm(y, lp, "attn_post_norm", cfg)
    h = _rmsnorm(x, lp["mlp_norm"], cfg.rms_eps)
    if ffn is None:
        g, u = mm(h, lp, "wg", "wu")
        (y,) = mm(jax.nn.silu(g) * u, lp, "wd")
    else:
        y = ffn(h, lp)
    return x + post_norm(y, lp, "mlp_post_norm", cfg), kept


def expert_stack(params):
    """The routed experts' weights of every expert layer end to end, ``[L *
    held, ...]`` (a bitcast of the ``[L, held, ...]`` stacks, which a model
    with conv layers keeps apart from its runs, in ``params["experts"]``):
    what :func:`moe_ffn` multiplies by, whole, the layer's own experts found
    in it at ``lp["expert_at"]``. A scan over the layers that took them as
    its ``xs`` would cut a layer's experts out of the stack every step, 1.8
    GB at Trinity's widths; the grouped products read them where they lie."""
    held = params.get("experts", params.get("layers"))
    return {n: held[n].reshape((-1,) + held[n].shape[2:])
            for n in EXPERT_WEIGHTS}


def moe_ffn(h, lp, cfg: LlamaConfig, experts, mm=products, valid=None):
    """The FFN of a dropless expert layer on the normed stream ``[b, s,
    h]``: ``Shared(h) + sum_{e in top-k} w_e Expert_e(h)``, routed over all
    ``num_experts``, computed for the experts held here
    (``transformer/moe.route``, ``dropless_experts``), whose weights are
    ``experts`` (:func:`expert_stack`) from group ``lp["expert_at"]`` on.
    ``valid [b, s]`` keeps padded positions and empty rows out of the
    routing. Returns ``(y, counts)``, ``counts`` int32 ``[2]``: the
    assignments on held experts and the held experts touched."""
    from apex_tpu.transformer.moe import dropless_experts, route

    xt = h.reshape(-1, h.shape[-1])
    w, idx = route(xt, lp["router"],
                   lp["router_bias"] if cfg.router_bias else None,
                   top_k=cfg.moe_top_k, score=cfg.router_score,
                   route_norm=cfg.route_norm, route_scale=cfg.route_scale,
                   norm_eps=cfg.route_norm_eps)
    y, counts = dropless_experts(
        xt, w, idx, experts, cfg.held,
        None if valid is None else valid.reshape(-1), lp["expert_at"])
    y = y.reshape(h.shape)
    if cfg.num_shared_experts:
        with jax.named_scope("moe/shared"):
            g, u = mm(h, lp, "shared_wg", "shared_wu")
            (shared,) = mm(jax.nn.silu(g) * u, lp, "shared_wd")
        y = y + shared
    return y, counts


def routed_block(x, lp, experts, cfg: LlamaConfig, positions, attend,
                 mm=products, valid=None):
    """:func:`block` on a layer of a dropless expert model, whichever stack
    it is of: the expert layer as its ``ffn`` where ``lp`` has a router
    (``experts``: :func:`expert_stack`), the dense SwiGLU where it has
    none. Returns ``(x, kept, counts)``, ``counts`` as :func:`moe_ffn`'s
    (zeros from a dense layer)."""
    counts = jnp.zeros(2, jnp.int32)
    ffn = None
    if "router" in lp:
        def ffn(h, lp):
            nonlocal counts
            y, counts = moe_ffn(h, lp, cfg, experts, mm, valid)
            return y

    x, kept = block(x, lp, cfg, positions, attend, mm, ffn)
    return x, kept, counts


def _moe_cfg(cfg: LlamaConfig):
    from apex_tpu.transformer.moe import MoEConfig

    return MoEConfig(hidden_size=cfg.hidden_size,
                     ffn_hidden_size=cfg.intermediate_size,
                     num_experts=cfg.num_experts, top_k=cfg.moe_top_k,
                     capacity_factor=cfg.moe_capacity_factor)


def _moe_mlp(x, lp, cfg: LlamaConfig, ep_axis, tp_axis, sequence_parallel):
    """Mixtral-style routed SwiGLU experts in place of the dense MLP.

    x arrives sequence-full and tp-replicated (every tp rank computes the
    same routing — experts shard over 'ep', orthogonal to tp; grads of the
    expert weights are therefore tp-identical). Returns (y, aux); in sp
    mode y is scattered back to the sequence-sharded stream.
    """
    from apex_tpu.transformer.moe import expert_parallel_apply

    def expert_fn(p, tokens):  # [E_local, C', h] -> [E_local, C', h]
        g = jnp.einsum("ech,ehf->ecf", tokens,
                       p["wg"].astype(tokens.dtype))
        u = jnp.einsum("ech,ehf->ecf", tokens,
                       p["wu"].astype(tokens.dtype))
        return jnp.einsum("ecf,efh->ech", jax.nn.silu(g) * u,
                          p["wd"].astype(tokens.dtype))

    y, aux = expert_parallel_apply(
        expert_fn, {"wg": lp["wg"], "wu": lp["wu"], "wd": lp["wd"]}, x,
        lp["router"], _moe_cfg(cfg), ep_axis=ep_axis)
    if sequence_parallel:
        y = scatter_to_sequence_parallel_region(y, tp_axis, seq_dim=1)
    return y, aux


def decoder_layer(x, lp, cfg: LlamaConfig, positions,
                  tp_axis: Optional[str] = "tp",
                  cp_axis: Optional[str] = "cp",
                  sequence_parallel: bool = False,
                  ep_axis: Optional[str] = "ep", experts=None):
    """:func:`block` for training, on [b, s_local, h]: q/k/v heads and the
    FFN's width tp-sharded, the sequence cp-sharded (ring attention when
    'cp' is bound), nothing kept. Returns ``(x, aux)`` — aux is the MoE
    load-balancing loss (0 dense).

    In sp mode the residual stream (and the norms) stay sequence-sharded;
    each half-block all-gathers the normed input ONCE for its column gemms
    and reduce-scatters the row-gemm output (Megatron sequence-parallel
    comm pattern: 2 gathers + 2 scatters per layer, not one per gemm).
    """
    tp = jax.lax.axis_size(tp_axis) if _axis_bound(tp_axis) else 1
    if cfg.num_heads % tp or cfg.num_kv_heads % tp:
        raise ValueError(
            f"tp={tp} must divide num_heads={cfg.num_heads} and "
            f"num_kv_heads={cfg.num_kv_heads}")

    def to_full(h):
        if sequence_parallel:
            return gather_from_sequence_parallel_region(h, tp_axis, seq_dim=1)
        return h

    if (cfg.dropless or cfg.windowed or cfg.hybrid) and (
            tp > 1 or sequence_parallel or _axis_bound(cp_axis)):
        raise NotImplementedError(
            "tensor, sequence and context parallelism of a model with "
            "dropless experts, sliding-window layers or conv layers are "
            "not built: its plain forward runs, nothing trains it yet")

    def mm(h, lp, *names):
        if names[0] in ("wo", "wd", "shared_wd"):  # row kernels: sharded input
            return [row_parallel_linear(
                h, lp[n], input_is_parallel=True,
                sequence_parallel_enabled=sequence_parallel,
                axis_name=tp_axis, seq_dim=1) for n in names]
        h = to_full(h)
        return [column_parallel_linear(h, lp[n], gather_output=False,
                                       axis_name=tp_axis) for n in names]

    def attend(q, k, v):
        if _axis_bound(cp_axis):
            # ring_attention is GQA-aware: k/v circulate at nkv heads
            return ring_attention(q, k, v, axis_name=cp_axis,
                                  causal=True), None
        # GQA-aware flash attention: online softmax, no [s, s] matrix in
        # HBM fwd or bwd (jnp fallback off-TPU is the same math)
        return causal_attention(q, k, v, lp, cfg), None

    if operator_of(lp) == "conv":       # a whole sequence, nothing kept
        def attend(u):
            return conv_window(cfg)(u)[0], None

    aux = jnp.zeros((), jnp.float32)

    def routed(h, lp):
        nonlocal aux
        y, aux = _moe_mlp(to_full(h), lp, cfg, ep_axis, tp_axis,
                          sequence_parallel)
        return y

    if cfg.dropless:
        x, _, _ = routed_block(x, lp, experts, cfg, positions, attend, mm)
        return x, aux
    x, _ = block(x, lp, cfg, positions, attend, mm,
                 routed if cfg.moe else None)
    return x, aux


def _positions(b, s_local, cp_axis):
    if _axis_bound(cp_axis):
        pos = context_parallel_positions(s_local, cp_axis)
    else:
        pos = jnp.arange(s_local)
    return jnp.broadcast_to(pos[None, :], (b, s_local))


def _layer_body(cfg: LlamaConfig, positions, tp_axis, cp_axis,
                sequence_parallel, remat, ep_axis, experts=None):
    """``(h, lp) -> (h, aux)`` for one layer, under the remat policy."""

    def body(h, lp):
        # aux rides the scan's stacked outputs, not the carry — a fresh
        # zero carry would need its vma hand-matched under shard_map
        return decoder_layer(h, lp, cfg, positions, tp_axis, cp_axis,
                             sequence_parallel, ep_axis, experts)

    if remat:
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if remat == "dots" else None)
        body = jax.checkpoint(body, policy=policy)
    return body


def _ep_varying(x, cfg: LlamaConfig, ep_axis):
    if cfg.moe and not cfg.dropless and _axis_bound(ep_axis):
        # the MoE all_to_all makes the stream ep-varying; the carry must
        # start that way or the scan's vma check trips
        from apex_tpu.transformer.tensor_parallel.mappings import (
            _to_varying,
        )

        x = _to_varying(x, ep_axis)
    return x


def run_layers(x, stacked, cfg: LlamaConfig, positions,
               tp_axis="tp", cp_axis="cp", sequence_parallel=False,
               remat=True, ep_axis: Optional[str] = "ep"):
    """Scan a stacked [L, ...] layer pytree over the residual stream, ONCE
    (a looped model's passes are :func:`scan_passes`'s).
    Returns ``(x, aux)`` — aux sums the per-layer MoE balance losses.

    ``remat``: False = save all activations; True = full per-layer
    recompute; ``"dots"`` = recompute only elementwise/norm chains while
    keeping matmul outputs resident
    (``jax.checkpoint_policies.dots_with_no_batch_dims_saveable``) — the
    usual best memory/MFU trade on TPU, where the recompute that hurts is
    the MXU work, not the VPU chains."""
    if cfg.dropless or cfg.windowed or cfg.hybrid:
        raise NotImplementedError(
            "run_layers scans one stack of one layer kind; a model with a "
            "dense lead, sliding-window layers or conv layers goes through "
            "scan_passes over stacks(params, cfg)")
    body = _layer_body(cfg, positions, tp_axis, cp_axis, sequence_parallel,
                       remat, ep_axis)
    x, auxs = jax.lax.scan(body, _ep_varying(x, cfg, ep_axis), stacked)
    return x, jnp.sum(auxs)


def stacks(params, cfg: LlamaConfig, **riders):
    """What :func:`scan_passes` takes as ``shared``: the model's layer
    stacks in the order a token goes through them. A model of one stack
    gives ``params["layers"]``; one with a dense lead gives the tuple
    ``(params["dense_layers"], params["layers"])``; one with conv layers the
    tuple of its runs (``params["runs"]``, one stack a run of ``cfg.runs``).
    ``riders`` are put among each stack's weights (the serving programs'
    ``scales``); where the model has sliding layers every stack also carries
    ``sliding``, its layers' kinds as a bool a layer, so that a scan step
    reads its kind as it reads its weights. A dropless expert stack goes
    without its routed experts' weights, which no scan may slice
    (:func:`expert_stack`), and carries ``expert_at``, where each layer's
    experts begin in them."""
    runs = params["runs"] if cfg.hybrid else [
        params[n] for n in ("dense_layers", "layers") if n in params]
    out, first, routed = [], 0, 0
    for stack in runs:
        if riders:
            stack = {**stack, **riders}
        n = stack["attn_norm"].shape[0]
        if cfg.dropless and "router" in stack:
            stack = {k: v for k, v in stack.items()
                     if k not in EXPERT_WEIGHTS}
            stack["expert_at"] = jnp.arange(
                routed, routed + n, dtype=jnp.int32) * cfg.held[1]
            routed += n
        if cfg.windowed:
            kinds = np.asarray(cfg.layer_types[first:first + n])
            stack = {**stack, "sliding": jnp.asarray(
                kinds == "sliding_attention")}
        out.append(stack)
        first += n
    return out[0] if len(out) == 1 and not cfg.hybrid else tuple(out)


def operator_of(stack) -> str:
    """Which operator a stack's (or a layer's) weights are of: ``"conv"`` or
    ``"attention"``. What a layer keeps follows it."""
    return "conv" if "conv_in" in stack else "attention"


def scan_passes(x, params, cfg: LlamaConfig, layer_fn, shared,
                per_layer=None):
    """Scan the residual stream through ``cfg.num_passes`` passes over the
    ``[L, ...]`` stack: ONE ``lax.scan`` of ``cfg.cache_layers`` steps.

    ``layer_fn(carry, shared_l, per_layer_i) -> (carry, out_i)`` applies one
    layer. ``x`` is the carry: the residual stream, or a tuple whose first
    member is the stream and whose others are state that every step may
    read and update where it lies (the serving decode step's page buffers:
    a scan's carry is updated in place, its ``xs`` and ``ys`` are separate
    arrays). ``shared`` is what every pass uses alike (a pytree with leading
    axis ``L``: the weights, their scales); ``per_layer`` is what each pass
    of each layer owns (leading axis ``cfg.cache_layers``, pass-major): K
    and V where a step consumes or produces them whole (``generate``'s
    contiguous cache), or just the steps' own indices into carried state.
    Step ``i`` is pass ``i // L`` of layer ``i % L``; every pass after the
    first starts from the final norm of the one before, applied to the
    stream alone, and the last pass's output is left for the head's own
    norm. Returns ``(carry, outs)``, ``outs`` stacked over the steps. With
    one pass this is the plain scan over ``(shared, per_layer)``: a model
    that is not looped compiles to what it did before.

    ``shared`` may be a tuple of stacks (:func:`stacks`: a dense lead, then
    the expert layers): one scan each, in turn, over its own slice of
    ``per_layer``; ``outs`` are the scans' laid end to end.

    A model with conv layers (``cfg.hybrid``) is such a tuple, of runs of two
    operators. What a layer owns and gives back follows its operator (K and
    V, or a conv state), so there ``per_layer`` and ``outs`` are dicts by
    operator (:func:`operator_of`), each with the leading axis of that
    operator's layers, in layer order: a run takes its slice of its own
    operator's entry, and the runs' outputs are laid end to end by operator.
    """
    L, T = cfg.num_layers, cfg.num_passes
    if isinstance(shared, tuple):
        outs, first = {}, {}
        for stack in shared:
            n, op = stack["attn_norm"].shape[0], operator_of(stack)
            at = first.get(op, 0)
            mine = jax.tree_util.tree_map(
                lambda a: a[at:at + n],
                per_layer[op] if cfg.hybrid and per_layer else per_layer)
            x, out = jax.lax.scan(lambda h, xs: layer_fn(h, *xs), x,
                                  (stack, mine))
            outs.setdefault(op, []).append(out)
            first[op] = at + n
        outs = {op: jax.tree_util.tree_map(lambda *a: jnp.concatenate(a), *o)
                for op, o in outs.items()}
        return x, outs if cfg.hybrid else outs["attention"]
    if T == 1:
        return jax.lax.scan(lambda h, xs: layer_fn(h, *xs), x,
                            (shared, per_layer))

    def body(carry, xs):
        i, mine = xs
        layer = i % L
        h, *state = carry if isinstance(carry, tuple) else (carry,)
        with jax.named_scope("llama/pass"):
            # one select a step on [.., h]: a cond would cost the loop more
            h = jnp.where((layer == 0) & (i > 0),
                          _rmsnorm(h, params["final_norm"], cfg.rms_eps), h)
            shared_l = jax.tree_util.tree_map(
                lambda w: jax.lax.dynamic_index_in_dim(w, layer,
                                                       keepdims=False),
                shared)
            return layer_fn((h, *state) if state else h, shared_l, mine)

    return jax.lax.scan(body, x, (jnp.arange(T * L), per_layer))


def embed(params, tokens, cfg: LlamaConfig, tp_axis="tp",
          sequence_parallel=False):
    x = vocab_parallel_embedding(tokens, params["embed"], axis_name=tp_axis)
    x = x.astype(cfg.dtype)
    if cfg.embed_scale != 1.0:
        x = (x.astype(jnp.float32) * cfg.embed_scale).astype(cfg.dtype)
    if sequence_parallel:
        x = scatter_to_sequence_parallel_region(x, tp_axis, seq_dim=1)
    return x


def lm_head_weight(params, cfg: LlamaConfig):
    """The [h, vocab] classifier kernel (embed.T when tied)."""
    return params["embed"].T if cfg.tie_embeddings else params["lm_head"]


def lm_head(params, x, cfg: LlamaConfig, tp_axis="tp",
            sequence_parallel=False):
    """Final norm + vocab-sharded logits [b, s, vocab/tp] (fp32)."""
    if sequence_parallel:
        x = gather_from_sequence_parallel_region(x, tp_axis, seq_dim=1)
    x = _rmsnorm(x, params["final_norm"], cfg.rms_eps)
    w = lm_head_weight(params, cfg)
    # vocab-sharded output: plain local gemm, no gather (CE is
    # vocab-parallel). Routed through the amp-aware hook: under the O4
    # fp8 context the registered "lm_head" site runs the E4M3/E5M2
    # delayed-scaling epilogue (the biggest single matmul in the step);
    # everywhere else this is the same fp32-accum gemm as before.
    from apex_tpu.ops.precision import matmul_amp

    return matmul_amp(x, w.astype(x.dtype),
                      name="lm_head").astype(jnp.float32)


def hidden_states(params, tokens, cfg: LlamaConfig,
                  tp_axis: Optional[str] = "tp",
                  cp_axis: Optional[str] = "cp",
                  sequence_parallel: bool = False, remat: bool = True,
                  ep_axis: Optional[str] = "ep"):
    """The shared model trunk: embed + all decoder layers (pre-final-norm).
    tokens [b, s_local] → (hidden [b, s_local, h], moe aux loss). Both
    loss paths (lm_head logits, chunked CE) consume this, so model
    changes land in each exactly once."""
    b, s = tokens.shape
    positions = _positions(b, s, cp_axis)
    x = embed(params, tokens, cfg, tp_axis, sequence_parallel)
    body = _layer_body(cfg, positions, tp_axis, cp_axis, sequence_parallel,
                       remat, ep_axis,
                       expert_stack(params) if cfg.dropless else None)
    x, auxs = scan_passes(_ep_varying(x, cfg, ep_axis), params, cfg,
                          lambda h, lp, _: body(h, lp), stacks(params, cfg))
    if cfg.hybrid:                      # the runs' losses, by operator
        auxs = jnp.concatenate(jax.tree_util.tree_leaves(auxs))
    return x, jnp.sum(auxs)


def exit_distribution(params, tokens, cfg: LlamaConfig,
                      tp_axis: Optional[str] = "tp",
                      cp_axis: Optional[str] = "cp", remat: bool = False):
    """A looped stack's exit gate read after every pass of the plain
    forward: ``(lam, p)``, both ``[T, b, s]`` float32. ``lam[t]`` is
    ``sigmoid(w . norm(h_t) + b)`` on pass ``t``'s normed output; ``p[t]``
    is the probability of leaving after pass ``t``: ``lam[t]`` times the
    share that has not left yet, and all of that share at the last pass,
    so ``p`` sums to 1. The rule "first pass whose cumulative ``p`` reaches
    ``early_exit_threshold``" picks the last pass at the published
    threshold of 1, which is what every other path computes. The passes
    are written out here, one ``run_layers`` each: a second wording of
    what :func:`scan_passes` rolls into one loop."""
    if "exit_gate" not in params:
        raise ValueError("exit_distribution needs a looped model "
                         "(num_passes > 1): the tree has no exit_gate")
    b, s = tokens.shape
    positions = _positions(b, s, cp_axis)
    gate = params["exit_gate"]
    x = embed(params, tokens, cfg, tp_axis)
    lams = []
    for _ in range(cfg.num_passes):
        x, _aux = run_layers(x, params["layers"], cfg, positions, tp_axis,
                             cp_axis, remat=remat, ep_axis=None)
        x = _rmsnorm(x, params["final_norm"], cfg.rms_eps)
        logit = jnp.matmul(x.astype(jnp.float32),
                           gate["w"].astype(jnp.float32))[..., 0]
        lams.append(jax.nn.sigmoid(logit + gate["b"].astype(jnp.float32)))
    lam = jnp.stack(lams)
    stay = jnp.cumprod(1.0 - lam, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]], axis=0)
    p = jnp.concatenate([(lam * before)[:-1], before[-1:]], axis=0)
    return lam, p


def forward_with_aux(params, tokens, cfg: LlamaConfig,
                     tp_axis: Optional[str] = "tp",
                     cp_axis: Optional[str] = "cp",
                     sequence_parallel: bool = False, remat: bool = True,
                     ep_axis: Optional[str] = "ep"):
    """tokens [b, s_local] → (vocab-sharded logits, moe aux loss)."""
    x, aux = hidden_states(params, tokens, cfg, tp_axis, cp_axis,
                           sequence_parallel, remat, ep_axis)
    return lm_head(params, x, cfg, tp_axis, sequence_parallel), aux


def forward(params, tokens, cfg: LlamaConfig,
            tp_axis: Optional[str] = "tp", cp_axis: Optional[str] = "cp",
            sequence_parallel: bool = False, remat: bool = True,
            ep_axis: Optional[str] = "ep"):
    """tokens [b, s_local] → vocab-sharded logits [b, s_local, v_local]."""
    return forward_with_aux(params, tokens, cfg, tp_axis, cp_axis,
                            sequence_parallel, remat, ep_axis)[0]


def loss_fn(params, batch, cfg: LlamaConfig,
            tp_axis: Optional[str] = "tp", cp_axis: Optional[str] = "cp",
            sequence_parallel: bool = False, remat: bool = True,
            ep_axis: Optional[str] = "ep",
            vocab_chunks: Optional[int] = None):
    """Next-token CE (+ MoE balance aux when cfg.moe);
    ``batch = (tokens, targets)`` both [b, s_local].

    ``vocab_chunks``: stream the lm-head + CE in that many vocab slices
    so the fp32 ``[b·s, vocab]`` logits — the largest live buffer of an
    LLM step — are never materialized (functional/chunked_ce.py). With a
    bound ``tp_axis`` the per-rank streams merge vocab-parallel."""
    if cfg.dropless or cfg.hybrid:
        raise NotImplementedError(
            "training a dropless expert model or a stack with conv layers "
            "is not built: the balance loss and the router bias's update "
            "are training's, the Pallas backward of a windowed flash call "
            "raises, and no gradient of a stack of two layer shapes is "
            "tested")
    tokens, targets = batch
    if vocab_chunks:
        from apex_tpu.transformer.functional.chunked_ce import (
            chunked_lm_cross_entropy,
        )

        x, aux = hidden_states(params, tokens, cfg, tp_axis, cp_axis,
                               sequence_parallel, remat, ep_axis)
        if sequence_parallel:
            x = gather_from_sequence_parallel_region(x, tp_axis, seq_dim=1)
        x = _rmsnorm(x, params["final_norm"], cfg.rms_eps)
        losses = chunked_lm_cross_entropy(
            x.reshape(-1, x.shape[-1]), lm_head_weight(params, cfg),
            targets.reshape(-1), vocab_chunks,
            tp_axis=tp_axis if _axis_bound(tp_axis) else None)
        return jnp.mean(losses) + aux
    logits, aux = forward_with_aux(params, tokens, cfg, tp_axis, cp_axis,
                                   sequence_parallel, remat, ep_axis)
    losses = vocab_parallel_cross_entropy(logits, targets, axis_name=tp_axis)
    return jnp.mean(losses) + aux


def param_specs(cfg: LlamaConfig, tp_axis: str = "tp",
                ep_axis: str = "ep"):
    """PartitionSpec pytree matching :func:`init_params` (tp sharding):
    column kernels split the output dim, row kernels the input dim, the
    embedding/head split the vocab dim, norms replicate."""
    from jax.sharding import PartitionSpec as P

    t = tp_axis
    norms = {"attn_norm": P(), "mlp_norm": P()}
    if cfg.sandwich_norm:
        norms.update(attn_post_norm=P(), mlp_post_norm=P())
    attention = {"wq": P(None, None, t), "wk": P(None, None, t),
                 "wv": P(None, None, t), "wo": P(None, t, None)}
    if cfg.qk_norm:
        attention.update(q_norm=P(), k_norm=P())
    if cfg.attn_output_gate:
        attention["wgate"] = P(None, None, t)
    # a conv layer's channels are its own: nothing of it is sharded
    conv = {"conv_in": P(), "conv_w": P(), "conv_out": P()}
    dense_ffn = {"wg": P(None, None, t), "wu": P(None, None, t),
                 "wd": P(None, t, None)}
    if cfg.dropless:
        # the experts held are this rank's own: nothing of them is sharded
        # further; the shared expert shards like a dense FFN
        routed_ffn = {"router": P(), "wg": P(), "wu": P(), "wd": P()}
        if cfg.router_bias:
            routed_ffn["router_bias"] = P()
        if cfg.num_shared_experts:
            routed_ffn.update(shared_wg=P(None, None, t),
                              shared_wu=P(None, None, t),
                              shared_wd=P(None, t, None))
    elif cfg.moe:
        # experts shard over ep_axis (orthogonal to tp); router replicates
        e = ep_axis
        routed_ffn = {"router": P(), "wg": P(None, e, None, None),
                      "wu": P(None, e, None, None),
                      "wd": P(None, e, None, None)}
    specs = {"embed": P(t, None), "final_norm": P()}
    if cfg.hybrid:
        runs = [{**norms, **(conv if operator == "conv" else attention),
                 **(routed_ffn if routed else dense_ffn)}
                for operator, routed, _ in cfg.runs]
        if cfg.moe:
            specs["experts"] = {n: P() for n in EXPERT_WEIGHTS}
            runs = [{k: v for k, v in run.items()
                     if not (kind[1] and k in EXPERT_WEIGHTS)}
                    for run, kind in zip(runs, cfg.runs)]
        specs["runs"] = tuple(runs)
    else:
        specs["layers"] = {**norms, **attention,
                           **(routed_ffn if cfg.moe else dense_ffn)}
        if cfg.dropless and cfg.num_dense_layers:
            specs["dense_layers"] = {**norms, **attention, **dense_ffn}
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, t)
    if cfg.num_passes > 1:
        specs["exit_gate"] = {"w": P(), "b": P()}
    return specs


# ------------------------------------------------------------- pipeline view


def stage_fn(stage_params, x, cfg: LlamaConfig, positions,
             tp_axis="tp", cp_axis=None, sequence_parallel=False,
             ep_axis: Optional[str] = "ep"):
    """Apply one pipeline stage's stacked layer slice to the residual
    stream — plug into ``pipeline_parallel.schedules`` (embedding/head live
    outside via :func:`embed`/:func:`lm_head` on the first/last stage).
    The MoE aux loss is dropped here: the pipeline transports activations
    only — train MoE stages with the aux folded in via :func:`loss_fn`
    style accounting outside pp, or accept routing without the balance
    regularizer under pp."""
    x, _ = run_layers(x, stage_params, cfg, positions, tp_axis, cp_axis,
                      sequence_parallel, remat=False, ep_axis=ep_axis)
    return x


def split_stages(params, n_stages: int):
    """Reshape stacked [L, ...] layers into [n_stages, L/n_stages, ...] for
    ``shard_map`` with ``in_specs=P('pp', ...)``."""
    def r(x):
        return x.reshape(n_stages, x.shape[0] // n_stages, *x.shape[1:])

    return jax.tree_util.tree_map(r, params["layers"])
