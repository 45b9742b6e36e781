"""Native host runtime bindings (SURVEY.md §2 #50).

ctypes loader for ``csrc/libapex_tpu_host.so`` plus pure-Python fallbacks
so the package works before ``make -C csrc`` has run. ``timing`` holds
the device timing helpers shared by bench.py and tools/.
"""

from apex_tpu.runtime import timing
from apex_tpu.runtime.host import (
    HostRuntime,
    PrefetchLoader,
    bucket_offsets,
    flatten_into,
    plan_buckets,
    runtime_available,
    unflatten_from,
)

__all__ = [
    "HostRuntime", "PrefetchLoader", "bucket_offsets", "flatten_into",
    "plan_buckets", "runtime_available", "timing", "unflatten_from",
]
