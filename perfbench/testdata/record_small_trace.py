#!/usr/bin/env python3
"""Records the small device trace kept beside this file (small_trace.xplane.pb).

Run once on the chip (`python3 perfbench/testdata/record_small_trace.py OUT_DIR`).
A scan of matmuls with the repo's LayerNorm and flash-attention kernels, three
calls under `bench:train_step` spans with a sleeping `bench:make_batch` span
between them, so the trace holds named kernels, a while loop, busy time and
idle gaps under known host spans. The tests of the trace reduction read it.
"""
import glob
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def main(out_dir):
    from apex_tpu.normalization.fused_layer_norm import fused_layer_norm_affine
    from apex_tpu.ops.flash_attention import flash_attention

    def small_step(x, w, g, b):
        def body(h, wl):
            h = fused_layer_norm_affine(h, g, b, (h.shape[-1],), eps=1e-5)
            q = h.reshape(2, 256, 4, 128)
            o = flash_attention(q, q, q, causal=True, scale=128 ** -0.5)
            return jnp.matmul(o.reshape(512, 512), wl), None
        return jax.lax.scan(body, x, w)[0]

    step = jax.jit(small_step)
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (512, 512), jnp.bfloat16)
    w = jax.random.normal(key, (4, 512, 512), jnp.bfloat16) * 0.05
    g, b = jnp.ones((512,), jnp.bfloat16), jnp.zeros((512,), jnp.bfloat16)
    jax.block_until_ready(step(x, w, g, b))
    trace_dir = os.path.join(out_dir, "small_trace_raw")
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench:make_batch"):
            time.sleep(0.004)
        with jax.profiler.TraceAnnotation("bench:train_step"):
            x = jax.block_until_ready(step(x, w, g, b))
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(trace_dir, "plugins/profile/*/*.xplane.pb"))
    shutil.copy(found[0], os.path.join(out_dir, "small_trace.xplane.pb"))
    print(found, os.path.getsize(found[0]))
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(found[0]).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", line.name, len(events))
            for e in events[:40]:
                print("     ", e.name[:90], e.start_ns, e.duration_ns,
                      [(k, str(v)[:40]) for k, v in list(e.stats)[:8]])


if __name__ == "__main__":
    os.makedirs(sys.argv[1], exist_ok=True)
    main(sys.argv[1])
