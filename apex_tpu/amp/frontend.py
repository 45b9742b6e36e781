"""amp frontend: opt levels O0–O3 and ``initialize`` — TPU re-design of
``apex.amp.frontend``.

Ref: apex/amp/frontend.py. The reference's opt levels configure (a) model
weight dtype, (b) torch-function patching, (c) master weights, (d) loss
scaling. The TPU mapping:

=====  ==================  =====================  ==============  ===========
level  param dtype         compute casting        master weights  loss scale
=====  ==================  =====================  ==============  ===========
O0     fp32                none                   no              1.0
O1     fp32                bf16 at op boundaries  no              dynamic
O2     bf16 (norms fp32)   bf16 params            fp32 (in opt)   dynamic
O3     bf16                pure bf16              no              1.0
O4     bf16 (norms fp32)   fp8 matmuls (E4M3/     fp32 (in opt)   dynamic
                           E5M2, delayed scaling)
=====  ==================  =====================  ==============  ===========

O4 (ISSUE 13) keeps O2's storage/master discipline and additionally
runs registered matmul sites in fp8 via
``apex_tpu.amp.scaler.Fp8DelayedScaler`` + ``ops.precision.matmul_fp8``
(see the fp8 table in lists.py and docs/amp.md — the delayed-scaling
state is separate, explicitly threaded through the train step).

bf16 replaces fp16 as the default "half" type (same MXU throughput, fp32
exponent range — the reason loss scaling is rarely *needed* on TPU, though
it is still fully supported; pass ``half_dtype=jnp.float16`` for strict
fp16 parity experiments). O1's torch-function monkeypatching has no XLA
analog — casting happens where ops are called, via :meth:`Policy.cast_to_compute`
and the fp32-internal fused kernels (see apex_tpu/amp/lists.py).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Union

import jax
import jax.numpy as jnp

from apex_tpu.amp._amp_state import _amp_state, maybe_print, warn_or_err
from apex_tpu.observability import scope

_NORM_KEY_HINTS = ("batchnorm", "bn", "layernorm", "rmsnorm", "norm", "scale_bias")


@dataclasses.dataclass
class Properties:
    """Resolved amp options (ref apex/amp/frontend.py:7 Properties)."""

    enabled: bool = False
    opt_level: Optional[str] = None
    cast_model_type: Optional[Any] = None     # param dtype (None = leave)
    patch_jax_functions: bool = False          # O1-style boundary casting
    keep_batchnorm_fp32: Optional[bool] = None
    master_weights: Optional[bool] = None
    loss_scale: Union[float, str] = 1.0
    fp8: bool = False                          # O4: fp8 matmul epilogues


def _opt_level_props(opt_level: str, half) -> Properties:
    if opt_level not in opt_levels:
        raise ValueError(
            f"Unexpected optimization level {opt_level}. Options are 'O0', "
            "'O1', 'O2', 'O3', 'O4'. Note that in `O0`, `O1`, etc., the "
            "prefix O is the letter O, not the number zero.")
    return opt_levels[opt_level](Properties(), half)


class O0:
    """Pure fp32 training (ref frontend.py O0 descriptor)."""

    brief = "O0: pure FP32 training.\n"
    more = ("Params stay fp32, no boundary casting, no loss scaling — the "
            "ground-truth baseline every other level is compared against.\n")

    def __call__(self, properties, half=jnp.bfloat16):
        properties.enabled = True
        properties.opt_level = "O0"
        properties.cast_model_type = jnp.float32
        properties.patch_jax_functions = False
        properties.keep_batchnorm_fp32 = None
        properties.master_weights = False
        properties.loss_scale = 1.0
        return properties


class O1:
    """Boundary casting, fp32 weights (ref frontend.py O1 descriptor)."""

    brief = "O1: insert automatic casts at op boundaries.\n"
    more = ("Weights stay fp32; MXU-friendly ops run in bf16 via the "
            "op-policy tables (apex_tpu/amp/lists.py) — the XLA analog of "
            "the reference's torch-function patching. The safest way to "
            "try mixed precision.\n")

    def __call__(self, properties, half=jnp.bfloat16):
        properties.enabled = True
        properties.opt_level = "O1"
        properties.cast_model_type = None
        properties.patch_jax_functions = True
        properties.keep_batchnorm_fp32 = None
        properties.master_weights = None
        properties.loss_scale = "dynamic"
        return properties


class O2:
    """Half weights + fp32 master weights (ref frontend.py O2)."""

    brief = "O2: 'almost half' — half model, fp32 master weights.\n"
    more = ("Params are cast to the half dtype (norm params stay fp32), "
            "the optimizer keeps fp32 master weights, dynamic loss "
            "scaling guards the update.\n")

    def __call__(self, properties, half=jnp.bfloat16):
        properties.enabled = True
        properties.opt_level = "O2"
        properties.cast_model_type = half
        properties.patch_jax_functions = False
        properties.keep_batchnorm_fp32 = True
        properties.master_weights = True
        properties.loss_scale = "dynamic"
        return properties


class O3:
    """Pure half training (ref frontend.py O3)."""

    brief = "O3: pure half-precision training.\n"
    more = ("Everything in the half dtype, no master weights, no loss "
            "scaling — the speed-of-light baseline for perf comparisons.\n")

    def __call__(self, properties, half=jnp.bfloat16):
        properties.enabled = True
        properties.opt_level = "O3"
        properties.cast_model_type = half
        properties.patch_jax_functions = False
        properties.keep_batchnorm_fp32 = False
        properties.master_weights = False
        properties.loss_scale = 1.0
        return properties


class O4:
    """fp8 (E4M3/E5M2) compute with delayed scaling (ISSUE 13)."""

    brief = "O4: fp8 matmuls (E4M3 fwd / E5M2 grad) with delayed scaling.\n"
    more = ("O2's storage discipline (bf16 model, fp32 norms + master "
            "weights, dynamic loss scale) plus fp8 matmul epilogues: "
            "registered sites quantize operands to E4M3 and backward "
            "cotangents to E5M2 under per-tensor delayed scales from "
            "AmaxHistory rings (apex_tpu.amp.scaler.Fp8DelayedScaler). "
            "The precision sanitizer rejects unsafe fp8 graphs "
            "statically (fp8-unscaled / fp8-stale-amax).\n")

    def __call__(self, properties, half=jnp.bfloat16):
        properties.enabled = True
        properties.opt_level = "O4"
        properties.cast_model_type = half
        properties.patch_jax_functions = False
        properties.keep_batchnorm_fp32 = True
        properties.master_weights = True
        properties.loss_scale = "dynamic"
        properties.fp8 = True
        return properties


opt_levels = {"O0": O0(), "O1": O1(), "O2": O2(), "O3": O3(),
              "O4": O4()}


@dataclasses.dataclass(frozen=True)
class Policy:
    """Dtype policy derived from an opt level (jmp-style three-dtype policy)."""

    param_dtype: Any
    compute_dtype: Any
    output_dtype: Any
    keep_batchnorm_fp32: bool = True

    def cast_to_compute(self, tree):
        """Cast activations/params entering a compute region (O1 boundary cast)."""
        return jax.tree_util.tree_map(
            lambda x: x.astype(self.compute_dtype)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)

    def cast_to_param(self, tree):
        return jax.tree_util.tree_map(
            lambda x: x.astype(self.param_dtype)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)

    def cast_to_output(self, tree):
        return jax.tree_util.tree_map(
            lambda x: x.astype(self.output_dtype)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)

    def cast_model(self, params):
        """Cast a model param tree to param_dtype, keeping norm/bn params fp32
        when ``keep_batchnorm_fp32`` (ref apex/amp/_initialize.py BN handling).

        Norm parameters are recognized by their flax module path (e.g.
        ``BatchNorm_0``, ``FusedLayerNorm_0``) — the tree-path analog of the
        reference's isinstance checks on module types.
        """
        flat = jax.tree_util.tree_flatten_with_path(params)[0]
        treedef = jax.tree_util.tree_structure(params)

        def cast_one(path, leaf):
            if not jnp.issubdtype(leaf.dtype, jnp.floating):
                return leaf
            if self.keep_batchnorm_fp32:
                keys = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path).lower()
                if any(h in keys for h in _NORM_KEY_HINTS):
                    return leaf.astype(jnp.float32)
            return leaf.astype(self.param_dtype)

        with scope("amp/cast_model"):
            leaves = [cast_one(path, leaf) for path, leaf in flat]
        return jax.tree_util.tree_unflatten(treedef, leaves)


def initialize(
    models=None,
    optimizers=None,
    enabled: bool = True,
    opt_level: str = "O1",
    cast_model_type=None,
    patch_jax_functions=None,
    keep_batchnorm_fp32=None,
    master_weights=None,
    loss_scale=None,
    min_loss_scale=None,
    max_loss_scale=2.0 ** 24,
    half_dtype=jnp.bfloat16,
    verbosity: int = 1,
    **kwargs,
):
    """Ref apex/amp/frontend.py:initialize (O0–O3 convenience wrapper).

    Functional JAX form: ``models`` is a params pytree (or None). Returns
    ``(cast_params, optimizers, handle)`` when params are given, else just
    the :class:`AmpHandle`. The handle carries the dtype :class:`Policy` and
    the functional :class:`LossScaler`; see ``apex_tpu/amp/handle.py``.
    """
    from apex_tpu.amp.handle import AmpHandle

    _amp_state.verbosity = verbosity
    props = _opt_level_props(opt_level, half_dtype)
    if not enabled:
        props.enabled = False
    # user overrides (ref frontend.py override block)
    if cast_model_type is not None:
        if props.opt_level == "O1" and cast_model_type not in (None, jnp.float32):
            warn_or_err("O1 keeps model weights fp32; use O2/O3 to cast weights.")
        props.cast_model_type = cast_model_type
    if patch_jax_functions is not None:
        props.patch_jax_functions = patch_jax_functions
    if keep_batchnorm_fp32 is not None:
        if isinstance(keep_batchnorm_fp32, str):
            keep_batchnorm_fp32 = keep_batchnorm_fp32 == "True"
        props.keep_batchnorm_fp32 = keep_batchnorm_fp32
    if master_weights is not None:
        props.master_weights = master_weights
    if loss_scale is not None:
        props.loss_scale = loss_scale

    maybe_print(f"Selected optimization level {opt_level}", True)

    handle = AmpHandle(props, min_loss_scale=min_loss_scale,
                       max_loss_scale=max_loss_scale, half_dtype=half_dtype)
    _amp_state.handle = handle
    _amp_state.opt_properties = props

    if models is None:
        return handle

    # disabled amp is a complete no-op (ref frontend.py: if not enabled, return
    # models/optimizers unchanged)
    cast_params = (
        handle.policy.cast_model(models)
        if (props.enabled and props.cast_model_type) else models)
    if optimizers is None:
        return cast_params, handle
    if props.enabled:  # disabled amp leaves the optimizer untouched too
        handle.attach(optimizers)
    return cast_params, optimizers, handle


def state_dict(destination=None):
    """Module-level amp checkpoint (ref apex/amp/frontend.py:state_dict)."""
    if _amp_state.handle is None:
        return {}
    return _amp_state.handle.state_dict()


def load_state_dict(state_dict_):
    """Ref apex/amp/frontend.py:load_state_dict."""
    if _amp_state.handle is None:
        raise RuntimeError("amp.initialize must be called before amp.load_state_dict")
    _amp_state.handle.load_state_dict(state_dict_)
