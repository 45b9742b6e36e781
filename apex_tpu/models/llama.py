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
heads' output) and ``embed_scale`` are written once, in :func:`block` and
:func:`embed`. With the defaults none of this is in any program.
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
    # each layer's attention, "sliding_attention" or "full_attention"; ()
    # is full attention everywhere. A sliding layer's query i reads keys
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

    def __post_init__(self):
        if self.layer_types and len(self.layer_types) != self.num_layers:
            raise ValueError(f"{len(self.layer_types)} layer_types for "
                             f"{self.num_layers} layers")
        if set(self.layer_types) - {"sliding_attention", "full_attention"}:
            raise ValueError(f"unknown layer type in {self.layer_types}")
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
    def cache_layers(self) -> int:
        """Layers of K and V behind ``num_layers`` layers of weights: pass
        ``t`` of layer ``l`` is cache layer ``t * num_layers + l``."""
        return self.num_passes * self.num_layers

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

    if cfg.dropless:
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


def _init_dropless(key, cfg: LlamaConfig):
    """:func:`init_params` of a dropless expert model: the dense lead in
    ``dense_layers`` (absent without one), the expert layers in ``layers``,
    each stacked on dim 0; the expert weights are those of
    ``cfg.experts_held`` alone, the router's all ``num_experts`` wide."""
    h, d, dt = cfg.hidden_size, cfg.head_dim, cfg.dtype
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    f = cfg.moe_intermediate_size or cfg.intermediate_size

    def norm(k, *shape, fan_in=None):
        return fan_in_normal(k, *shape, fan_in=fan_in, dtype=dt)

    def stack(key, n, ffn):
        ks = jax.random.split(key, 6)
        out = {"attn_norm": jnp.ones((n, h), dt),
               "mlp_norm": jnp.ones((n, h), dt),
               "wq": norm(ks[0], n, h, nq * d),
               "wk": norm(ks[1], n, h, nkv * d),
               "wv": norm(ks[2], n, h, nkv * d),
               "wo": norm(ks[3], n, nq * d, h)}
        if cfg.sandwich_norm:
            out["attn_post_norm"] = jnp.ones((n, h), dt)
            out["mlp_post_norm"] = jnp.ones((n, h), dt)
        if cfg.qk_norm:
            out["q_norm"] = jnp.ones((n, d), dt)
            out["k_norm"] = jnp.ones((n, d), dt)
        if cfg.attn_output_gate:
            out["wgate"] = norm(ks[4], n, h, nq * d)
        out.update(ffn(ks[5], n))
        return out

    def dense(key, n):
        k = jax.random.split(key, 3)
        i = cfg.intermediate_size
        return {"wg": norm(k[0], n, h, i), "wu": norm(k[1], n, h, i),
                "wd": norm(k[2], n, i, h)}

    def routed(key, n):
        k = jax.random.split(key, 8)
        held, fs = cfg.held[1], f * cfg.num_shared_experts
        out = {"router": (jax.random.normal(k[0], (n, h, cfg.num_experts))
                          * 0.02).astype(dt),
               "wg": norm(k[1], n, held, h, f),
               "wu": norm(k[2], n, held, h, f),
               "wd": norm(k[3], n, held, f, h)}
        if cfg.router_bias:
            out["router_bias"] = jnp.zeros((n, cfg.num_experts),
                                           jnp.float32)
        if fs:
            out.update(shared_wg=norm(k[4], n, h, fs),
                       shared_wu=norm(k[5], n, h, fs),
                       shared_wd=norm(k[6], n, fs, h))
        return out

    ks = jax.random.split(key, 4)
    params = {"embed": norm(ks[0], cfg.vocab_size, h, fan_in=h),
              "layers": stack(ks[1], cfg.expert_layers, routed),
              "final_norm": jnp.ones((h,), dt)}
    if cfg.num_dense_layers:
        params["dense_layers"] = stack(ks[2], cfg.num_dense_layers, dense)
    if not cfg.tie_embeddings:
        params["lm_head"] = norm(ks[3], h, cfg.vocab_size, fan_in=h)
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


def block(x, lp, cfg: LlamaConfig, positions, attend, mm=products, ffn=None):
    """The Llama decoder block on one layer's (unstacked) weights ``lp``:
    the one place the sub-layer sequence is written. Training, ``generate``
    and both serving programs call it and pass in what differs:

    - ``attend(q, k, v) -> (o, kept)``: attention on the rotated heads
      (``q`` ``[b, s, nq, d]``, ``k`` and ``v`` ``[b, s, nkv, d]``) and where
      K and V live: nowhere (training), returned whole (the prefills), put
      into a cache that is then attended to (the decode steps). ``kept`` is
      handed back beside the stream, untouched.
    - ``mm(x, lp, *names)``: ``x`` times each named weight, an iterable of
      one product a name. The names of one call share their input, so a
      hook that has to gather it (sequence parallelism) does so once a
      half-block.
    - ``ffn(h, lp)``: the feed-forward on the normed stream; without one,
      the dense SwiGLU through ``mm``.

    What the config states of the attention block is here too: the norm on
    each query and key head (``qk_norm``), the rotation, which a layer of
    a kind that does not rotate passes by (:func:`_rotate`), and the
    sigmoid gate on the heads' output (``attn_output_gate``). The window of
    a sliding layer is ``attend``'s: it alone knows the keys.

    Returns ``(x, kept)``."""
    d = cfg.head_dim
    h = _rmsnorm(x, lp["attn_norm"], cfg.rms_eps)
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
    held, ...]`` (a bitcast of the ``[L, held, ...]`` stacks): what
    :func:`moe_ffn` multiplies by, whole, the layer's own experts found in
    it at ``lp["expert_at"]``. A scan over the layers that took them as its
    ``xs`` would cut a layer's experts out of the stack every step, 1.8 GB
    at Trinity's widths; the grouped products read them where they lie."""
    return {n: params["layers"][n].reshape(
        (-1,) + params["layers"][n].shape[2:]) for n in ("wg", "wu", "wd")}


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
                   route_norm=cfg.route_norm, route_scale=cfg.route_scale)
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

    if (cfg.dropless or cfg.windowed) and (
            tp > 1 or sequence_parallel or _axis_bound(cp_axis)):
        raise NotImplementedError(
            "tensor, sequence and context parallelism of a model with "
            "dropless experts or sliding-window layers are not built: its "
            "plain forward runs, nothing trains it yet")

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
    if cfg.dropless or cfg.windowed:
        raise NotImplementedError(
            "run_layers scans one stack of one layer kind; a model with a "
            "dense lead or sliding-window layers goes through scan_passes "
            "over stacks(params, cfg)")
    body = _layer_body(cfg, positions, tp_axis, cp_axis, sequence_parallel,
                       remat, ep_axis)
    x, auxs = jax.lax.scan(body, _ep_varying(x, cfg, ep_axis), stacked)
    return x, jnp.sum(auxs)


def stacks(params, cfg: LlamaConfig, **riders):
    """What :func:`scan_passes` takes as ``shared``: the model's layer
    stacks in the order a token goes through them. A model of one stack
    gives ``params["layers"]``; one with a dense lead gives the tuple
    ``(params["dense_layers"], params["layers"])``. ``riders`` are put
    among each stack's weights (the serving programs' ``scales``); where
    the model has sliding layers every stack also carries ``sliding``, its
    layers' kinds as a bool a layer, so that a scan step reads its kind as
    it reads its weights. A dropless expert stack goes without its routed
    experts' weights, which no scan may slice (:func:`expert_stack`), and
    carries ``expert_at``, where each layer's experts begin in them."""
    names = [n for n in ("dense_layers", "layers") if n in params]
    out, first = [], 0
    for name in names:
        stack = {**params[name], **riders} if riders else params[name]
        n = stack["attn_norm"].shape[0]
        if cfg.dropless and "router" in stack:
            stack = {k: v for k, v in stack.items()
                     if k not in ("wg", "wu", "wd")}
            stack["expert_at"] = jnp.arange(n, dtype=jnp.int32) * cfg.held[1]
        if cfg.windowed:
            kinds = np.asarray(cfg.layer_types[first:first + n])
            stack = {**stack, "sliding": jnp.asarray(
                kinds == "sliding_attention")}
        out.append(stack)
        first += n
    return out[0] if len(out) == 1 else tuple(out)


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
    """
    L, T = cfg.num_layers, cfg.num_passes
    if isinstance(shared, tuple):
        outs, first = [], 0
        for stack in shared:
            n = stack["attn_norm"].shape[0]
            mine = jax.tree_util.tree_map(lambda a: a[first:first + n],
                                          per_layer)
            x, out = jax.lax.scan(lambda h, xs: layer_fn(h, *xs), x,
                                  (stack, mine))
            outs.append(out)
            first += n
        return x, jax.tree_util.tree_map(
            lambda *a: jnp.concatenate(a), *outs)
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
    if cfg.dropless:
        raise NotImplementedError(
            "training a dropless expert model is not built: its balance "
            "loss and the router bias's update are training's, and the "
            "Pallas backward of a windowed flash call raises")
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
    layer_specs = {
        "attn_norm": P(), "mlp_norm": P(),
        "wq": P(None, None, t), "wk": P(None, None, t),
        "wv": P(None, None, t), "wo": P(None, t, None),
    }
    if cfg.sandwich_norm:
        layer_specs.update(attn_post_norm=P(), mlp_post_norm=P())
    if cfg.qk_norm:
        layer_specs.update(q_norm=P(), k_norm=P())
    if cfg.attn_output_gate:
        layer_specs["wgate"] = P(None, None, t)
    dense_ffn = {"wg": P(None, None, t), "wu": P(None, None, t),
                 "wd": P(None, t, None)}
    lead = None
    if cfg.dropless:
        # the experts held are this rank's own: nothing of them is sharded
        # further; the shared expert shards like a dense FFN
        if cfg.num_dense_layers:
            lead = {**layer_specs, **dense_ffn}
        layer_specs.update(router=P(), wg=P(), wu=P(), wd=P())
        if cfg.router_bias:
            layer_specs["router_bias"] = P()
        if cfg.num_shared_experts:
            layer_specs.update(shared_wg=P(None, None, t),
                               shared_wu=P(None, None, t),
                               shared_wd=P(None, t, None))
    elif cfg.moe:
        # experts shard over ep_axis (orthogonal to tp); router replicates
        e = ep_axis
        layer_specs.update({
            "router": P(),
            "wg": P(None, e, None, None),
            "wu": P(None, e, None, None),
            "wd": P(None, e, None, None),
        })
    else:
        layer_specs.update(dense_ffn)
    specs = {
        "embed": P(t, None),
        "layers": layer_specs,
        "final_norm": P(),
    }
    if lead is not None:
        specs["dense_layers"] = lead
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
