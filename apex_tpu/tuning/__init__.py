"""apex_tpu.tuning — Pallas kernel autotuner (ISSUE 6 / ROADMAP item 3).

Every Pallas kernel *earns* its tiling and its dispatch verdict per
device: search spaces are declared (VMEM-bounded) in
:mod:`~apex_tpu.tuning.search_space`, candidates are raced against the
XLA fallback by :mod:`~apex_tpu.tuning.measure` (real on-device
races on TPU, the kernel-cost-study roofline model as the deterministic
CPU fallback), and winners persist in a schema-versioned JSON cache
(:mod:`~apex_tpu.tuning.cache`) keyed by ``(device_kind, kernel,
shape-bucket)``. Dispatch (``pallas_config.flash_blocks`` /
``use_pallas`` and the kernels' geometry lookups in
:mod:`~apex_tpu.tuning.geometry`) consults the cache, so a tuned entry
both picks the tile and flips the ``_KERNEL_AUTO`` verdict — with the
cache file as the provenance evidence artifact.

Offline tune-everything: ``python -m apex_tpu.tuning`` / tools/tune.sh.
"""

from apex_tpu.tuning.cache import (  # noqa: F401
    SCHEMA_VERSION,
    apply_verdicts,
    cache_path,
    entries_for,
)
from apex_tpu.tuning.cache import load as load_cache  # noqa: F401
from apex_tpu.tuning.cache import save as save_cache  # noqa: F401
from apex_tpu.tuning.geometry import (  # noqa: F401
    flash_tiles,
    flat_adam_geometry,
    fp8_cast_geometry,
    norm_row_block,
    override,
    softmax_block_k,
)
from apex_tpu.tuning.search_space import (  # noqa: F401
    KERNELS,
    candidates,
    shape_bucket,
)
from apex_tpu.tuning.tuner import (  # noqa: F401
    DEFAULT_SHAPES,
    tune_all,
    tune_kernel,
)
