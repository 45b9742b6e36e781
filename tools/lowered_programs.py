#!/usr/bin/env python
"""Write down what the Llama programs lower to, so that two checkouts can be
compared without a chip: a refactor of the model is neutral when the text is
the same.

    python tools/lowered_programs.py --repo /root/scratch/parent --out /root/scratch/low/parent
    python tools/lowered_programs.py --out /root/scratch/low/change
    python tools/lowered_programs.py --compare /root/scratch/low/parent /root/scratch/low/change

One file a program, StableHLO without locations:

- ``serve.<cell>.decode`` / ``.prefill<S>``: ``_decode_step`` and one
  prefill bucket of every serving cell of ``BENCHMARK.json``, at the cell's
  engine shapes, lowered for a described v5e with the Pallas kernels on. A
  Mosaic kernel's serialized body carries the paths and lines of its source,
  so it is written as the SHA-256 of its assembly without them.
  ``--aot`` also compiles them for the v5e and writes ``<name>.aot.json``:
  ``memory_analysis()``, the compiled program's instructions by kind, and
  the SHA-256 of its text without metadata: equal digests are one schedule,
  one set of fusions under the same names. Each serving program's line, and
  its ``.aot.json``, says how many grouped products of the expert layers it
  holds and what computes them (``apex_gmm``, ``ragged_dot``).
- ``generate.<stack>``: ``models.generate.generate`` at ``llama.tiny()``.
- ``grad.<stack>.<mesh>``: the gradient of ``llama.loss_fn`` at
  ``llama.tiny()``, unbound and under a two-device ``shard_map`` on the CPU.

``--compare`` reports, a program: ``same text``; or ``same ops``, the same
lines once value names are stripped and the lines sorted (independent
operations traced in another order); or ``DIFFERENT``. It exits 1 on any
``DIFFERENT`` and on any ``.aot.json`` that is not equal.
"""

import argparse
import base64
import collections
import hashlib
import inspect
import json
import os
import pathlib
import re
import sys

HERE = pathlib.Path(__file__).resolve().parent.parent
SERVING_CELLS = (("mistral7b_v03_d16", "longgen_backlog"),
                 ("mistral7b_v03_d16", "longprompt_poisson"),
                 ("ouro_2p6b", "reasoning_backlog"),
                 ("trinity_large_ep8_d5", "longctx_backlog"),
                 ("lfm2_24b_a2b_d9", "docextract_backlog"))


def serving_programs():
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from apex_tpu.models import llama
    from apex_tpu.ops import pallas_config
    from apex_tpu.serving import kv_cache, scheduler as sched
    from perfbench import harness
    import importlib

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def read(kind, name):
        return harness.load_json(harness.ROOT, "perfbench", kind,
                                 name + ".json")

    jax.config.update("jax_enable_compilation_cache", False)
    for config, mix in SERVING_CELLS:
        if not os.path.exists(os.path.join(
                harness.ROOT, "perfbench", "configs", config + ".json")):
            continue                 # a checkout from before the cell
        raw = read("configs", config)
        cfg = importlib.import_module(
            "perfbench.runners." + raw["runner"]).model_config(raw)
        eng = read("traffic", mix)["engine"]
        page, rows = eng["page_size"], eng["max_batch"]
        table = sched.pages_per_request(eng["max_prompt_len"],
                                        eng["max_new_cap"], page)
        params = jax.tree_util.tree_map(
            lambda a: struct(a.shape, a.dtype),
            jax.eval_shape(
                lambda: llama.init_params(jax.random.PRNGKey(0), cfg)))
        # a page's minor dims as the cache lays them (heads side by side
        # where they are narrow), and a conv model's state buffer beside
        dims = (kv_cache.page_dims(cfg) if hasattr(kv_cache, "page_dims")
                else (cfg.num_kv_heads, cfg.head_dim))
        pages = struct((cfg.cache_layers, eng["num_pages"] + 1, page, *dims),
                       cfg.dtype)
        held = [pages, pages]
        bucket = eng["max_prompt_len"]
        step = sched.build_decode_step(cfg, page)
        if "conv_state" in inspect.signature(step).parameters:
            # since PR 35: the conv layers' state, None where there are none
            held.append(struct((cfg.conv_layers, cfg.conv_L_cache - 1, rows,
                                cfg.hidden_size), cfg.dtype)
                        if cfg.hybrid else None)
        batch = [struct((rows,), jnp.int32), struct((rows, table), jnp.int32),
                 struct((rows,), jnp.int32), struct((rows,), jnp.bool_)]
        if len(inspect.signature(step).parameters) >= 10:
            # since PR 34: the step before's tokens as the device holds them
            # (the counts an expert model appends with them), and the host's
            # patch for the rows admitted since
            batch[0] = struct(
                (rows + 2 * bool(getattr(cfg, "dropless", False)),), jnp.int32)
            batch += [struct((rows,), jnp.bool_), struct((rows,), jnp.int32)]
        with pallas_config.force("on"):
            programs = {
                "decode": jax.jit(step, donate_argnums=tuple(
                    range(2, 2 + len(held)))).lower(
                    params, {}, *held, *batch),
                f"prefill{bucket}": sched.build_prefill(cfg, bucket).lower(
                    params, {}, struct((1, bucket), jnp.int32),
                    struct((), jnp.int32))}
            for name, lowered in programs.items():
                yield f"serve.{config}.{mix}.{name}", lowered


def cpu_programs():
    import dataclasses
    import functools

    import jax
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from apex_tpu.models import generate, llama

    tiny = llama.tiny()
    stacks = {
        "plain": tiny,
        "looped": dataclasses.replace(tiny, num_passes=4, sandwich_norm=True),
        "moe": dataclasses.replace(tiny, num_experts=4)}
    tokens = jax.ShapeDtypeStruct((2, 16), np.int32)
    for name, cfg in stacks.items():
        params = jax.eval_shape(
            lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
        yield f"generate.{name}", jax.jit(
            functools.partial(generate.generate, cfg=cfg,
                              max_new_tokens=8)).lower(params, tokens)

        # unbound, then one axis at a time over two devices; sp rides tp
        meshes = {"unbound": {}, "tp2": {"tp_axis": "tp"},
                  "tp2sp": {"tp_axis": "tp", "sequence_parallel": True},
                  "cp2": {"cp_axis": "cp"}}
        if cfg.moe:
            meshes["ep2"] = {"ep_axis": "ep"}
        for mesh_name, bound in meshes.items():
            axes = {"tp_axis": None, "cp_axis": None, "ep_axis": None,
                    **bound}
            loss = functools.partial(llama.loss_fn, cfg=cfg, **axes)
            grad = jax.grad(lambda p, t: loss(p, (t, t)))
            if bound:
                axis = next(a for a in bound.values() if isinstance(a, str))
                specs = llama.param_specs(
                    cfg, tp_axis=axes["tp_axis"], ep_axis=axes["ep_axis"])
                batch = P(None, "cp") if axis == "cp" else P()
                grad = jax.shard_map(
                    grad, mesh=Mesh(np.array(jax.devices()[:2]), (axis,)),
                    in_specs=(specs, batch), out_specs=specs,
                    check_vma=True)
            yield f"grad.{name}.{mesh_name}", jax.jit(grad).lower(params, tokens)


def aot_record(lowered):
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    kinds = collections.Counter()
    program = []
    for line in without_kernel_locations(compiled.as_text()).splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \S+ ([\w\-]+)\(", line)
        if m:
            fused = re.search(r"kind=(k\w+)", line)
            kinds[m.group(1) + (":" + fused.group(1) if fused else "")] += 1
        # the tables of files, functions and stack frames are numbered rows
        if not re.match(r"\s*(\d+ [\"{]|(File|Function)Names$|"
                        r"FileLocations$|StackFrames$)", line):
            program.append(re.sub(r",? ?metadata=\{[^}]*\}", "", line))
    return {"memory": {k: getattr(memory, k) for k in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "alias_size_in_bytes", "temp_size_in_bytes")},
            "instructions_by_kind": dict(sorted(kinds.items())),
            "program_sha256": hashlib.sha256(
                "\n".join(program).encode()).hexdigest()}


def without_kernel_locations(text):
    """``text`` with every Mosaic body replaced by a digest of its assembly
    printed without debug information."""
    if "tpu_custom_call" not in text:
        return text
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True

    def digest(match):
        body = base64.b64decode(match.group(2))
        try:
            with ctx:
                module = ir.Module.parse(body)
                body = module.operation.get_asm(
                    enable_debug_info=False).encode()
        except Exception:
            # a kernel of XLA's own (its ragged dot) that this MLIR cannot
            # parse: it carries no path of this repo, so as it is
            pass
        return match.group(1) + "sha256:" + hashlib.sha256(body).hexdigest()

    # as StableHLO escapes the quotes, and as compiled HLO prints them
    return re.sub(r'(\\22body\\22: \\22|"body":")([A-Za-z0-9+/=]+)', digest, text)


def grouped_products(text):
    """How many grouped products of the dropless expert layers a lowered
    program holds, by what computes them: the Pallas kernel `apex_gmm`
    (`ops/grouped_matmul`) or XLA's `ragged_dot`. The choice is static a
    program, so this is the count that says the kernel engages: three a scan
    body of expert layers, none in a dense model's programs. A kernel stands
    once in the function that holds it (`grouped_matmul._call` is jitted), so
    an occurrence counts as often as its function is called."""
    bodies = dict(re.findall(
        r"func\.func \w+ @([\w.]+)\((.*?)(?=\n  func\.func |\Z)", text, re.S))
    calls = {name: collections.Counter(re.findall(r"call @([\w.]+)\(", body))
             for name, body in bodies.items()}

    def times_run(name, seen=()):
        if name == "main":
            return 1
        return sum(n[name] * times_run(caller, seen + (name,))
                   for caller, n in calls.items()
                   if n[name] and caller not in seen)

    def count(pattern):
        return sum(len(re.findall(pattern, body)) * times_run(name)
                   for name, body in bodies.items())

    return {"apex_gmm": count(r'kernel_name = "apex_gmm"'),
            "ragged_dot": count(r"= \"?chlo\.ragged_dot\b")}


def write(out, which, aot):
    out.mkdir(parents=True, exist_ok=True)
    sources = {"cpu": cpu_programs, "serving": serving_programs}
    for source in sources if which == "all" else (which,):
        for name, lowered in sources[source]():
            text = without_kernel_locations(lowered.as_text())
            (out / f"{name}.mlir").write_text(text)
            said = ""
            if source == "serving":
                products = grouped_products(text)
                said = ": " + ", ".join(f"{n} {what}" for what, n
                                        in products.items())
                if aot:
                    (out / f"{name}.aot.json").write_text(json.dumps(
                        {**aot_record(lowered),
                         "grouped_products": products}, indent=1))
            print(f"wrote {name}{said}", flush=True)


def ops(text):
    """The program's lines without the names of values, sorted."""
    return sorted(re.sub(r"%[\w#:]+", "%", line.strip())
                  for line in text.splitlines())


def compare(a, b):
    names = sorted({p.name for d in (a, b) for p in d.iterdir()})
    worst = 0
    for name in names:
        if not ((a / name).exists() and (b / name).exists()):
            verdict, bad = "only on one side", 1
        else:
            ta, tb = (a / name).read_text(), (b / name).read_text()
            if ta == tb:
                verdict, bad = "same text", 0
            elif name.endswith(".mlir") and ops(ta) == ops(tb):
                verdict, bad = "same ops", 0
            else:
                verdict, bad = "DIFFERENT", 1
        worst |= bad
        print(f"{verdict:18s}{name}")
    return worst


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--repo", default=str(HERE),
                    help="the checkout whose apex_tpu is lowered")
    ap.add_argument("--out", type=pathlib.Path)
    ap.add_argument("--which", choices=("all", "cpu", "serving"),
                    default="all")
    ap.add_argument("--aot", action="store_true",
                    help="compile the serving programs for the v5e too")
    ap.add_argument("--compare", nargs=2, type=pathlib.Path,
                    metavar=("PARENT", "CHANGE"))
    args = ap.parse_args()
    if args.compare:
        return compare(*args.compare)
    if not args.out:
        ap.error("--out or --compare")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=2")
    sys.path.insert(0, args.repo)
    write(args.out, args.which, args.aot)
    return 0


if __name__ == "__main__":
    sys.exit(main())
