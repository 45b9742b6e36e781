"""apex_tpu — a TPU-native re-imagining of NVIDIA Apex.

Everything Apex offers for CUDA/PyTorch (mixed precision, fused optimizers,
fused normalization, data/tensor/pipeline parallelism) rebuilt TPU-first on
JAX/XLA/Pallas: functional transforms, ``jax.sharding.Mesh`` + ``shard_map``
for parallelism, Pallas kernels for the hot ops, and XLA collectives
(psum / all_gather / ppermute / reduce_scatter) over the ICI mesh instead of
NCCL.

Reference capability surface: /root/reference (NVIDIA Apex); see SURVEY.md §2
for the component-by-component mapping.
"""

import logging


class RankInfoFormatter(logging.Formatter):
    """ref apex/__init__.py:28 — logging formatter injecting the current
    (tp, pp, dp, ...) rank tuple into every record; pairs with
    ``transformer.log_util.set_logging_level`` for multi-rank runs."""

    def format(self, record):
        from apex_tpu.transformer.parallel_state import get_rank_info
        try:
            record.rank_info = get_rank_info()
        except Exception:  # outside an initialized mesh
            record.rank_info = "-"
        return super().format(record)


from apex_tpu import amp
from apex_tpu import observability
from apex_tpu import optimizers
from apex_tpu import normalization
from apex_tpu import parallel
from apex_tpu import multi_tensor_apply
from apex_tpu import transformer
from apex_tpu import fp16_utils
from apex_tpu import fused_dense
from apex_tpu import mlp
from apex_tpu import models
from apex_tpu import pyprof
from apex_tpu import reparameterization
from apex_tpu import resilience
from apex_tpu import rnn

__version__ = "0.1.0"

__all__ = [
    "RankInfoFormatter",
    "amp",
    "optimizers",
    "normalization",
    "parallel",
    "multi_tensor_apply",
    "observability",
    "transformer",
    "fp16_utils",
    "fused_dense",
    "mlp",
    "models",
    "pyprof",
    "reparameterization",
    "resilience",
    "rnn",
]
