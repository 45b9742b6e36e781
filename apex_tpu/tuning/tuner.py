"""Tile-sweep tuner: race every candidate, persist winners + verdicts.

``tune_kernel`` sweeps one kernel's search space at one shape, races the
best Pallas candidate against the XLA fallback, and writes the result
into the persistent cache — tile AND dispatch verdict, so a tuned entry
is the evidence artifact that flips ``pallas_config._KERNEL_AUTO``.
``tune_all`` is the offline tune-everything entry point behind
``tools/tune.sh`` (and ``python -m apex_tpu.tuning``).

Telemetry: every race ticks ``tuning/race_won_pallas`` or
``tuning/race_won_xla`` (labeled by kernel) and sets
``tuning/best_pallas_ms`` / ``tuning/xla_ms`` gauges, so bench runs land
the tuning story in BENCH_METRICS.jsonl next to the perf numbers.
"""

from __future__ import annotations

import sys

from apex_tpu.tuning import cache, measure, search_space

# Default sweep shapes: the bench.py kernel-race shapes (the workloads
# whose dispatch the cache will actually serve). n is the GPT-2-345M
# flat-buffer size from bench.make_params.
DEFAULT_SHAPES = {
    "flat_adam": {"n": 356515840},
    "flash_attention_fwd": {"bh": 64, "sq": 2048, "sk": 2048, "d": 128,
                            "causal": True},
    "flash_attention_bwd": {"bh": 64, "sq": 2048, "sk": 2048, "d": 128,
                            "causal": True},
    "layer_norm": {"rows": 8192, "h": 4096},
    "rms_norm": {"rows": 8192, "h": 4096},
    "fused_softmax": {"rows": 256, "sk": 32768},
    # the llama lm_head activation at the bench shapes: (B*S, hidden) =
    # 8 * 2048 * 4096 — the biggest tensor the O4 tier quantizes per step
    "fp8_cast": {"n": 8 * 2048 * 4096},
}


def _registry(registry=None):
    if registry is not None:
        return registry
    from apex_tpu.observability import get_registry

    return get_registry()


def tune_kernel(kernel, dims=None, *, live=None, cache_dict=None,
                write=True, apply=True, registry=None, log=None):
    """Sweep ``kernel`` at ``dims``; returns the result record.

    ``live=None`` auto-detects (real race on TPU, roofline off-TPU).
    ``cache_dict`` accumulates results across calls (tune_all); with
    ``write`` the cache file is saved and — when ``apply`` — the race
    verdict is flipped into pallas_config with the cache file as its
    evidence artifact.
    """
    if kernel not in search_space.KERNELS:
        raise ValueError(f"unknown kernel {kernel!r}; valid: "
                         f"{list(search_space.KERNELS)}")
    dims = dict(DEFAULT_SHAPES[kernel] if dims is None else dims)
    if live is None:
        live = measure.backend_is_tpu()
    reg = _registry(registry)
    log = log or (lambda msg: print(msg, file=sys.stderr))

    # one set of measurement inputs for the whole sweep (the flat_adam
    # carry is ~5.7 GB — regenerating it per candidate would spend the
    # chip time on RNG, not races)
    runner = measure.live_runner(kernel, dims) if live else None
    ranked = []
    for params in search_space.candidates(kernel, **dims):
        try:
            t = measure.measure(kernel, params, dims, live=live,
                                runner=runner)
        except Exception as e:  # noqa: BLE001 — one Mosaic-rejected
            # candidate must not kill the sweep; it just can't win
            log(f"tune {kernel} {params}: FAILED {repr(e)[:120]}")
            reg.counter("tuning/candidate_error", kernel=kernel).inc()
            continue
        ranked.append((t, sorted(params.items())))
        log(f"tune {kernel} {params}: {t * 1e3:.3f} ms")
    if not ranked:
        raise RuntimeError(f"every {kernel} candidate failed to measure")
    ranked.sort()  # (time, params) — deterministic tie-break on params
    best_t, best_params = ranked[0][0], dict(ranked[0][1])
    xla_t = measure.measure_xla(kernel, dims, live=live, runner=runner)

    won = best_t <= xla_t
    reg.counter("tuning/race_won_pallas" if won else "tuning/race_won_xla",
                kernel=kernel).inc()
    bucket = search_space.shape_bucket(kernel, **{
        k: v for k, v in dims.items() if k not in ("bh", "causal")})
    reg.gauge("tuning/best_pallas_ms", kernel=kernel,
              bucket=bucket).set(round(best_t * 1e3, 4))
    reg.gauge("tuning/xla_ms", kernel=kernel,
              bucket=bucket).set(round(xla_t * 1e3, 4))
    entry = {
        "params": best_params,
        "pallas_ms": round(best_t * 1e3, 4),
        "xla_ms": round(xla_t * 1e3, 4),
        "use_pallas": bool(won),
        "source": "measured" if live else "roofline",
        "dims": dims,
    }
    device_kind = cache.current_device_kind()
    reg.event("tuning_result", kernel=kernel, bucket=bucket,
              device_kind=device_kind, **{
                  k: v for k, v in entry.items() if k != "dims"})
    log(f"tune {kernel}: best {best_params} "
        f"pallas {best_t * 1e3:.3f} ms vs xla {xla_t * 1e3:.3f} ms "
        f"-> {'pallas' if won else 'xla'} [{entry['source']}]")

    result = {"kernel": kernel, "bucket": bucket,
              "device_kind": device_kind, "entry": entry,
              "ranking": [(round(t * 1e3, 4), dict(p))
                          for t, p in ranked]}
    if cache_dict is not None:
        cache.put(cache_dict, device_kind, kernel, bucket, entry)
    if write:
        # always merge into the CURRENT on-disk cache: saving a bare
        # accumulator would destroy every entry another device (or an
        # earlier run) already measured
        target = cache.load()
        if cache_dict is not None:
            cache.merge(target, cache_dict)
        else:
            cache.put(target, device_kind, kernel, bucket, entry)
        path = cache.save(target)
        result["cache_path"] = path
        if apply:
            result["applied_verdicts"] = cache.apply_verdicts(path)
    return result


def tune_all(shapes=None, *, kernels=None, live=None, write=True,
             apply=True, registry=None, log=None):
    """Sweep every registered kernel — or just ``kernels`` — with
    ``shapes`` overriding per-kernel dims, and persist one merged cache
    write at the end. Returns the list of per-kernel results; a kernel
    whose whole sweep fails is recorded, not fatal — an offline tune
    run must report every kernel it could."""
    shapes = shapes or {}
    acc = cache.load()
    results = []
    for kernel in (kernels or search_space.KERNELS):
        try:
            results.append(tune_kernel(
                kernel, shapes.get(kernel), live=live, cache_dict=acc,
                write=False, registry=registry, log=log))
        except Exception as e:  # noqa: BLE001
            results.append({"kernel": kernel, "error": repr(e)[:200]})
    if write:
        path = cache.save(cache.merge(cache.load(), acc))
        for r in results:
            r["cache_path"] = path
        if apply:
            applied = cache.apply_verdicts(path)
            for r in results:
                r.setdefault("applied_verdicts", applied)
    return results
