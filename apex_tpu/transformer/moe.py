"""Mixture-of-Experts with expert parallelism over an 'ep' mesh axis.

No reference-file analog (SURVEY.md §1 lists 'ep' among the comms-layer
mesh axes the TPU design must serve; the CUDA reference predates MoE).
The design is the GShard/Switch formulation, which is TPU-first by
construction — everything is static-shaped einsums the MXU eats directly:

- router: softmax over experts, top-1 (Switch) or top-2 (GShard) gating
  with the standard load-balancing auxiliary loss;
- dispatch/combine: one-hot [tokens, experts, capacity] masks — no
  sorting, no dynamic shapes; tokens beyond an expert's capacity are
  dropped (scaled by capacity_factor);
- expert parallelism: experts shard over 'ep'; inside ``shard_map`` a pair
  of ``all_to_all`` collectives swaps the token dimension for the expert
  dimension and back, so each rank runs only its local experts (the NCCL
  analog would be torch all_to_all; here XLA schedules it on ICI).

Layout summary (per ep rank, T = local tokens, E = global experts,
C = per-expert capacity):

    x [T, h] --dispatch--> [E, C, h] --all_to_all--> [E_local, n*C, h]
      --expert mlp--> [E_local, n*C, h] --all_to_all--> [E, C, h]
      --combine--> [T, h]

That is the layer as it is trained. :func:`dropless_experts` is the layer
as it is deployed: token choice with no capacity, so no token is ever
dropped; a chip that is told which of the experts it holds routes over all
of them and computes its own experts' part of the result, by grouped matrix
products over the assignments sorted by expert.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from apex_tpu.ops import grouped_matmul, pallas_config
# dispatch/combine/expert einsums contract over the (large) token and
# capacity axes — bf16 partial sums there lose real gate mass, so the
# accumulator is pinned >= fp32 (apex_tpu.analysis lowprec-accum)
from apex_tpu.ops.precision import einsum_fp32acc as _ein_fp32acc
from apex_tpu.transformer.tensor_parallel.mappings import _axis_bound

EXPERT_AXIS = "ep"


class MoEConfig(NamedTuple):
    hidden_size: int
    ffn_hidden_size: int
    num_experts: int
    top_k: int = 2                 # 1 = Switch, 2 = GShard
    capacity_factor: float = 1.25
    router_jitter: float = 0.0     # optional exploration noise (training)
    aux_loss_coef: float = 1e-2
    # router z-loss (ST-MoE §4, arXiv:2202.08906): penalizes large router
    # logits, which destabilize bf16 training; 0 disables (default)
    z_loss_coef: float = 0.0


def init_moe_params(key, cfg: MoEConfig, dtype=jnp.float32):
    """router [h, E] + per-expert MLP weights stacked on dim 0.

    Shard for ep with ``P('ep', ...)`` on the expert-stacked weights;
    the router replicates.
    """
    kr, k1, k2 = jax.random.split(key, 3)
    h, f, e = cfg.hidden_size, cfg.ffn_hidden_size, cfg.num_experts
    lim1 = (6.0 / (h + f)) ** 0.5
    return {
        "router": (jax.random.normal(kr, (h, e)) * 0.02).astype(dtype),
        "wi": jax.random.uniform(k1, (e, h, f), dtype, -lim1, lim1),
        "wo": jax.random.uniform(k2, (e, f, h), dtype, -lim1, lim1),
    }


def moe_param_specs(cfg: MoEConfig, ep_axis: str = EXPERT_AXIS):
    from jax.sharding import PartitionSpec as P

    return {"router": P(), "wi": P(ep_axis, None, None),
            "wo": P(ep_axis, None, None)}


def _capacity(tokens: int, cfg: MoEConfig) -> int:
    cap = int(tokens * cfg.top_k * cfg.capacity_factor / cfg.num_experts)
    return max(cap, cfg.top_k)


def router_gates(logits, cfg: MoEConfig, with_stats: bool = False):
    """Top-k gating with position-in-expert assignment (GShard algo).

    logits [T, E] -> (combine [T, E, C], dispatch [T, E, C], aux_loss).
    All shapes static; tokens past an expert's capacity get zero gates
    (dropped — the residual stream carries them unchanged).

    ``aux_loss`` is the scalar TOTAL auxiliary loss (load-balance +
    optional z-loss) so callers can add it straight to the task loss.
    ``with_stats=True`` appends a telemetry dict
    ``{"dropped_frac", "balance_loss", "z_loss"}`` — dropped_frac is the
    fraction of the T·k routing assignments that fell past an expert's
    capacity (the production drop-rate signal a capacity_factor is tuned
    against).
    """
    t, e = logits.shape
    c = _capacity(t, cfg)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # [T, E]

    combine = jnp.zeros((t, e, c), jnp.float32)
    remaining = probs
    # cumulative per-expert fill across the k choices
    fill = jnp.zeros((e,), jnp.int32)
    gates_sum = jnp.zeros((t,), jnp.float32)
    pieces = []
    for _ in range(cfg.top_k):
        idx = jnp.argmax(remaining, axis=-1)                     # [T]
        onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)       # [T, E]
        gate = jnp.sum(probs * onehot, axis=-1)                  # [T]
        # position of each token within its chosen expert's queue:
        # running count of earlier tokens (any k-th choice) + earlier
        # choices' fill
        pos = (jnp.cumsum(onehot, axis=0) - onehot) + fill[None, :]
        pos_t = jnp.sum(pos * onehot, axis=-1).astype(jnp.int32)  # [T]
        keep = pos_t < c
        gate = gate * keep.astype(jnp.float32)
        pieces.append((onehot, gate, pos_t, keep))
        fill = fill + jnp.sum(onehot, axis=0).astype(jnp.int32)
        gates_sum = gates_sum + gate
        remaining = remaining * (1.0 - onehot)

    # top-k>1: normalize the kept gates to sum to 1 per token (GShard /
    # Mixtral combine). top-1 keeps the RAW probability (Switch eq. 2):
    # normalizing would make the gate a constant 1 and kill the router's
    # task-loss gradient — it would learn from the balance loss only.
    if cfg.top_k == 1:
        denom = jnp.ones_like(gates_sum)
    else:
        denom = jnp.maximum(gates_sum, 1e-9)
    for onehot, gate, pos_t, keep in pieces:
        slot = jax.nn.one_hot(pos_t, c, dtype=jnp.float32)       # [T, C]
        contrib = (gate / denom)[:, None, None] * onehot[:, :, None] \
            * slot[:, None, :]
        combine = combine + jnp.where(keep[:, None, None], contrib, 0.0)

    dispatch = combine > 0.0

    # load-balancing aux loss (Switch eq. 4): E * mean_frac . mean_prob
    first_onehot = pieces[0][0]
    frac = jnp.mean(first_onehot, axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    balance = cfg.aux_loss_coef * e * jnp.sum(frac * mean_prob)

    # router z-loss (ST-MoE eq. 5): mean (logsumexp of the fp32 logits)^2.
    # cfg.z_loss_coef is a static float: skip the logsumexp (+ backward)
    # entirely at the 0.0 default — 0*z is not DCE-safe for XLA
    if cfg.z_loss_coef:
        z_loss = cfg.z_loss_coef * jnp.mean(jax.scipy.special.logsumexp(
            logits.astype(jnp.float32), axis=-1) ** 2)
    else:
        z_loss = jnp.zeros((), jnp.float32)
    aux = balance + z_loss
    if not with_stats:
        return combine, dispatch, aux

    kept = sum(jnp.sum(keep.astype(jnp.float32))
               for _, _, _, keep in pieces)
    stats = {
        "dropped_frac": 1.0 - kept / (t * cfg.top_k),
        "balance_loss": balance,
        "z_loss": z_loss,
    }
    return combine, dispatch, aux, stats


def expert_parallel_apply(expert_fn, expert_params, x, router,
                          cfg: MoEConfig,
                          ep_axis: Optional[str] = EXPERT_AXIS,
                          router_key=None, with_stats: bool = False):
    """Route tokens through per-expert functions; returns (y, aux_loss).

    ``expert_fn(expert_params, tokens)`` maps [E_local, C', h] ->
    [E_local, C', h] with the LOCAL experts' stacked params (any
    structure — a dict of stacked weights works). Inside ``shard_map``
    with ``ep_axis`` bound the dispatch swaps the expert dim for the
    token dim with a pair of tiled all_to_all collectives so each rank
    runs only its experts; without the axis everything runs locally
    (identical math). This is the layer other modules build on — e.g.
    the Llama Mixtral-style SwiGLU experts — while :func:`moe_mlp` is
    the plain two-matmul MLP instance.

    ``with_stats=True`` returns ``(y, aux_loss, stats)`` (see
    :func:`router_gates`); inside ``shard_map`` the stats are per-rank —
    ``pmean`` them over the dp/ep axes for global telemetry.
    """
    lead = x.shape[:-1]
    h = x.shape[-1]
    xt = x.reshape(-1, h)

    logits = jnp.matmul(xt.astype(jnp.float32), router.astype(jnp.float32))
    if router_key is not None and cfg.router_jitter > 0.0:
        logits = logits * jax.random.uniform(
            router_key, logits.shape, jnp.float32,
            1.0 - cfg.router_jitter, 1.0 + cfg.router_jitter)
    gated = router_gates(logits, cfg, with_stats=with_stats)
    combine, dispatch, aux = gated[:3]

    expert_in = _ein_fp32acc("tec,th->ech", dispatch.astype(xt.dtype), xt)

    if _axis_bound(ep_axis):
        # [E, C, h] -> [E/n, n*C, h]: send expert-chunk j to rank j, gather
        # every rank's C-token slab for my local experts along capacity.
        # tiled=True is load-bearing: untiled all_to_all STACKS a new rank
        # axis instead of concatenating tiles, which silently broadcasts
        # against the local expert dim whenever E/n == 1
        expert_in = jax.lax.all_to_all(
            expert_in, ep_axis, split_axis=0, concat_axis=1, tiled=True)

    y = expert_fn(expert_params, expert_in)

    if _axis_bound(ep_axis):
        # inverse: [E/n, n*C, h] -> [E, C, h]; capacity slab j returns to
        # rank j, expert chunks re-concatenate in global expert order
        y = jax.lax.all_to_all(y, ep_axis, split_axis=1, concat_axis=0,
                               tiled=True)

    out = _ein_fp32acc("tec,ech->th", combine.astype(xt.dtype), y)
    out = out.reshape(*lead, h).astype(x.dtype)
    if with_stats:
        return out, aux.astype(jnp.float32), gated[3]
    return out, aux.astype(jnp.float32)


def moe_mlp(params, x, cfg: MoEConfig, ep_axis: Optional[str] = EXPERT_AXIS,
            activation=jax.nn.gelu, router_key=None,
            with_stats: bool = False):
    """MoE feed-forward on [..., h]; returns (y, aux_loss).

    Inside ``shard_map`` with ``ep_axis`` bound, experts run
    expert-parallel: params['wi']/'wo' hold only the LOCAL experts
    ([E/n, ...], sharded with :func:`moe_param_specs`) while the router
    and dispatch math see all E experts. Without a bound axis it runs all
    experts locally (single-device semantics, same math).
    """

    def expert_fn(p, tokens):
        y = _ein_fp32acc("ech,ehf->ecf", tokens,
                         p["wi"].astype(tokens.dtype))
        y = activation(y)
        return _ein_fp32acc("ecf,efh->ech", y,
                            p["wo"].astype(tokens.dtype))

    return expert_parallel_apply(
        expert_fn, {"wi": params["wi"], "wo": params["wo"]}, x,
        params["router"], cfg, ep_axis=ep_axis, router_key=router_key,
        with_stats=with_stats)


# ------------------------------------------------- the layer as deployed


def route(x, router, bias=None, *, top_k: int, score: str = "softmax",
          route_norm: bool = True, route_scale: float = 1.0,
          norm_eps: float = 1e-20):
    """Token-choice routing of ``x [T, h]`` over ALL ``E`` experts of
    ``router [h, E]``: ``(weights [T, k] float32, experts [T, k] int32)``.

    Scores are the softmax or the sigmoid of the float32 logits. ``bias``
    ``[E]`` enters the selection only: the ``top_k`` are taken of ``score +
    bias``, the weights are the scores themselves. ``route_norm`` divides a
    token's weights by their sum plus ``norm_eps`` (as published: 1e-20, or
    1e-6); ``route_scale`` multiplies them. Softmax
    with ``route_norm`` and ``k > 1`` is Mixtral's gate
    (``generate._moe_router_weights``)."""
    if score not in ("softmax", "sigmoid"):
        raise ValueError(f"router score must be softmax or sigmoid, "
                         f"got {score!r}")
    with jax.named_scope("moe/route"):
        logits = jnp.matmul(x.astype(jnp.float32),
                            router.astype(jnp.float32))
        scores = (jax.nn.sigmoid(logits) if score == "sigmoid"
                  else jax.nn.softmax(logits, axis=-1))
        chosen = scores if bias is None else scores + bias.astype(
            jnp.float32)
        _, idx = jax.lax.top_k(chosen, top_k)
        w = jnp.take_along_axis(scores, idx, axis=-1)
        if route_norm:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + norm_eps)
        return w * route_scale, idx.astype(jnp.int32)


def dropless_experts(x, weights, idx, experts, held=None, valid=None, at=0):
    """The routed part of an expert layer for the experts held here:
    ``sum_k weights[t, k] * Expert_{idx[t, k]}(x[t])`` over the assignments
    that fall on experts ``first .. first + count - 1`` (``held = (first,
    count)``; all of them without it). ``experts`` holds the SwiGLU weights
    of exactly those, stacked: ``wg``, ``wu`` ``[count, h, f]``, ``wd``
    ``[count, f, h]``. What the other experts would add is left out: on a
    chip of an expert-parallel deployment it is another chip's part, and
    nothing here stands in for that chip or for the exchange with it.

    ``experts`` may hold the experts of several layers end to end, ``[G, h,
    f]`` with this layer's ``count`` from group ``at`` on (``at`` may be
    traced: a scan step's own), every group of another layer empty: a layer
    scan reads its experts where they lie, with no ``[count, h, f]`` slice
    cut out of the stack a step.

    No capacity and no drop, at static shapes: the ``T * k`` assignments
    are sorted by expert, those to experts not held last; each of the three
    products is one grouped product over the sorted rows, grouped by the
    held experts' counts: on the TPU the Pallas kernel ``apex_gmm``
    (``ops/grouped_matmul.gmm``, which walks this layer's ``count`` groups
    and the rows they have, under ``pallas_config.use_pallas("gmm")``),
    elsewhere ``jax.lax.ragged_dot`` over all ``G`` groups; the rows are put
    back in order, weighted and summed a token (in float32, slot by slot: a
    token's result is made of its own rows alone). ``valid [T]`` leaves a
    token's assignments out (a padded position, an empty batch row).

    Returns ``(y [T, h], counts)``; ``counts`` is int32 ``[2]``: the
    assignments that fell on held experts, and the held experts that got at
    least one."""
    t, k = idx.shape
    groups = experts["wg"].shape[0]
    first, count = (0, groups) if held is None else held
    if count > groups:
        raise ValueError(f"{count} experts held, weights of {groups}")
    with jax.named_scope("moe/experts"):
        local = idx.reshape(-1) - first
        mine = (local >= 0) & (local < count)
        if valid is not None:
            mine = mine & jnp.repeat(valid, k)
        key = jnp.where(mine, at + local, groups)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        sizes = jnp.zeros(groups + 1, jnp.int32).at[key].add(1)[:groups]
        xs = x[order // k]
        wg, wu, wd = (experts[n].astype(x.dtype) for n in ("wg", "wu", "wd"))
        if pallas_config.use_pallas("gmm"):
            # the layer's own groups alone, found in the stack at `at`
            product = functools.partial(grouped_matmul.gmm, sizes=sizes,
                                        at=at, count=count)
        else:
            product = functools.partial(jax.lax.ragged_dot,
                                        group_sizes=sizes)
        g, u = product(xs, wg), product(xs, wu)
        ys = product(jax.nn.silu(g) * u, wd)
        back = jnp.zeros_like(order).at[order].set(
            jnp.arange(t * k, dtype=jnp.int32))
        # a row past the groups belongs to no product: whatever it holds,
        # it counts for nothing
        y = jnp.where(mine[:, None], ys[back].astype(jnp.float32), 0.0)
        y = jnp.sum(y.reshape(t, k, -1) * jnp.where(
            mine.reshape(t, k), weights, 0.0)[..., None], axis=1)
        counts = jnp.stack([jnp.sum(mine, dtype=jnp.int32),
                            jnp.sum(sizes > 0, dtype=jnp.int32)])
        return y.astype(x.dtype), counts
