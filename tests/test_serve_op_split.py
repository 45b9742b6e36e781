"""`tools/serve_op_split.py`: the compiled texts of an engine's programs from
the shapes of what it holds, and a made-up trace's operations booked to the
program they ran in and named by that program's scopes (PERF.md §5, PR 35)."""

import importlib.util
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tests", "run_serving"))
spec = importlib.util.spec_from_file_location(
    "serve_op_split", os.path.join(ROOT, "tools", "serve_op_split.py"))
tool = importlib.util.module_from_spec(spec)
spec.loader.exec_module(tool)


def test_operations_are_booked_to_their_program_and_named_by_its_scopes():
    from apex_tpu import observability as obs
    from apex_tpu.observability.profiling.hlo_scopes import op_scopes
    from apex_tpu.serving import ServingEngine
    from perfbench import trace_reduce as tr
    from perfbench.references import lfm2_moe as ref
    from perfbench.references.common import seed_words
    from test_lfm2_serving import CFG, TINY

    engine = ServingEngine(ref.init(*seed_words(7), TINY), CFG, page_size=4,
                           max_batch=2, num_pages=12, max_prompt_len=8,
                           max_new_cap=4, registry=obs.MetricRegistry())
    engine.submit(np.arange(6, dtype=np.int32), 3)
    engine.run()
    texts = tool.programs_text(engine.scheduler)
    assert sorted(texts) == ["decode", "prefill8"]
    named = {kind: {scope for _, scope in op_scopes(text).values()}
             for kind, text in texts.items()}
    for kind in texts:
        assert any("llama/short_conv" in s for s in named[kind])
        assert any("llama/attention" in s for s in named[kind])
        assert any("moe/experts" in s for s in named[kind])
        assert any("llama/head" in s for s in named[kind])
    conv = next(name for name, (_, scope) in op_scopes(
        texts["decode"]).items() if scope.endswith("llama/short_conv"))
    assert tool.kind_of("jit__serving_prefill_s2560") == "prefill2560"
    assert tool.kind_of("jit__decode_step") == "decode"
    assert tool.kind_of("jit__serving_write_pages") is None
    ms = 1_000_000
    trace = tr.Trace(
        window=(0, 100 * ms),
        modules={0: [("jit__decode_step", 0, 10 * ms),
                     ("jit__serving_prefill_s8", 20 * ms, 30 * ms),
                     ("jit__decode_step", 60 * ms, 12 * ms),
                     ("jit__serving_write_pages", 80 * ms, ms)]},
        ops={0: [(conv, ms, 2 * ms), ("ragged-dot-none.1", 4 * ms, 3 * ms),
                 ("%apex_gmm.7", 8 * ms, ms), ("apex_gmm", 66 * ms, 4 * ms),
                 (conv, 61 * ms, 4 * ms), ("no_such_op", 25 * ms, 5 * ms),
                 ("outside_any_program", 90 * ms, ms)]})
    got = tool.split(trace, texts)
    assert (got["decode"]["runs"], got["decode"]["ms"]) == (2, 11.0)
    scope = next(s for s in got["decode"]["scopes"] if "short_conv" in s)
    assert got["decode"]["scopes"][scope] == 3.0
    assert got["decode"]["scopes"]["ragged-dot"] == 1.5
    # the Pallas grouped product by its name, whatever scope it stands in
    assert got["decode"]["scopes"]["apex_gmm"] == 2.5
    assert got["prefill8"] == {"runs": 1, "ms": 30.0,
                               "scopes": {"(no scope)": 5.0}}
