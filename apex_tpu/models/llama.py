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
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

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
    moe_capacity_factor: float = 1.25
    # looped stack: the [L, ...] layers run this many times over the same
    # weights, the final norm between passes (Ouro's ``total_ut_steps``)
    num_passes: int = 1
    # RMSNorm on each sub-layer's output too, before the residual add
    sandwich_norm: bool = False

    @property
    def moe(self) -> bool:
        return self.num_experts > 0

    @property
    def cache_layers(self) -> int:
        """Layers of K and V behind ``num_layers`` layers of weights: pass
        ``t`` of layer ``l`` is cache layer ``t * num_layers + l``."""
        return self.num_passes * self.num_layers

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads


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

    Returns ``(x, kept)``."""
    d = cfg.head_dim
    h = _rmsnorm(x, lp["attn_norm"], cfg.rms_eps)
    q, k, v = (y.reshape(*y.shape[:2], -1, d)
               for y in mm(h, lp, "wq", "wk", "wv"))
    q, k = apply_rotary_qk(q, k, positions=positions, base=cfg.rope_theta)
    o, kept = attend(q, k, v)
    (y,) = mm(o.reshape(*o.shape[:2], -1), lp, "wo")
    x = x + post_norm(y, lp, "attn_post_norm", cfg)
    h = _rmsnorm(x, lp["mlp_norm"], cfg.rms_eps)
    if ffn is None:
        g, u = mm(h, lp, "wg", "wu")
        (y,) = mm(jax.nn.silu(g) * u, lp, "wd")
    else:
        y = ffn(h, lp)
    return x + post_norm(y, lp, "mlp_post_norm", cfg), kept


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
                  ep_axis: Optional[str] = "ep"):
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

    def mm(h, lp, *names):
        if names[0] in ("wo", "wd"):     # row kernels: the input is sharded
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
        return flash_attention(q, k, v, causal=True,
                               scale=cfg.head_dim ** -0.5), None

    aux = jnp.zeros((), jnp.float32)

    def routed(h, lp):
        nonlocal aux
        y, aux = _moe_mlp(to_full(h), lp, cfg, ep_axis, tp_axis,
                          sequence_parallel)
        return y

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
                sequence_parallel, remat, ep_axis):
    """``(h, lp) -> (h, aux)`` for one layer, under the remat policy."""

    def body(h, lp):
        # aux rides the scan's stacked outputs, not the carry — a fresh
        # zero carry would need its vma hand-matched under shard_map
        return decoder_layer(h, lp, cfg, positions, tp_axis, cp_axis,
                             sequence_parallel, ep_axis)

    if remat:
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if remat == "dots" else None)
        body = jax.checkpoint(body, policy=policy)
    return body


def _ep_varying(x, cfg: LlamaConfig, ep_axis):
    if cfg.moe and _axis_bound(ep_axis):
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
    body = _layer_body(cfg, positions, tp_axis, cp_axis, sequence_parallel,
                       remat, ep_axis)
    x, auxs = jax.lax.scan(body, _ep_varying(x, cfg, ep_axis), stacked)
    return x, jnp.sum(auxs)


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
    """
    L, T = cfg.num_layers, cfg.num_passes
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
                       remat, ep_axis)
    x, auxs = scan_passes(_ep_varying(x, cfg, ep_axis), params, cfg,
                          lambda h, lp, _: body(h, lp), params["layers"])
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
    if cfg.moe:
        # experts shard over ep_axis (orthogonal to tp); router replicates
        e = ep_axis
        layer_specs.update({
            "router": P(),
            "wg": P(None, e, None, None),
            "wu": P(None, e, None, None),
            "wd": P(None, e, None, None),
        })
    else:
        layer_specs.update({
            "wg": P(None, None, t), "wu": P(None, None, t),
            "wd": P(None, t, None),
        })
    specs = {
        "embed": P(t, None),
        "layers": layer_specs,
        "final_norm": P(),
    }
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
