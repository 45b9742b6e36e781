"""Engine 2 (AST) unit tests: true-positive snippet + idiomatic clean
snippet per check, plus suppression syntax and the baseline machinery."""

import collections

import pytest

from apex_tpu.analysis import lint_source
from apex_tpu.analysis.findings import (
    Finding,
    new_findings,
    save_baseline,
    load_baseline,
)


def _lint(src, checks=None):
    return lint_source(src, "snippet.py", checks)


def _by_check(findings, check):
    return [f for f in findings if f.check == check]


# ------------------------------------------------------------ sync-timing

def test_sync_timing_flagged():
    src = """
import time, jax

def bench_step(fn, x):
    t0 = time.perf_counter()
    out = fn(x)
    jax.block_until_ready(out)
    return time.perf_counter() - t0
"""
    found = _by_check(_lint(src), "sync-timing")
    assert len(found) == 1
    assert found[0].line == 7 and found[0].symbol == "bench_step"
    assert "timing.sync" in found[0].message


def test_sync_timing_method_call_and_module_scope():
    src = """
import time, jax
t0 = time.perf_counter()
out.block_until_ready()
print(time.perf_counter() - t0)
"""
    found = _by_check(_lint(src), "sync-timing")
    assert len(found) == 1 and found[0].symbol == "<module>"
    # the module-scope pass must honor the checks= narrowing too
    assert not _lint(src, checks=("mutable-default",))


def test_sync_timing_sees_aliased_clock_imports():
    """`from time import time` / `import time as t` are still clock
    reads — the r5 bug class must not slip through an import alias."""
    src = """
import jax
from time import time

def bench_step(fn, x):
    t0 = time()
    jax.block_until_ready(fn(x))
    return time() - t0
"""
    assert len(_by_check(_lint(src), "sync-timing")) == 1
    src2 = """
import jax
import time as t

def bench_step(fn, x):
    t0 = t.time()
    jax.block_until_ready(fn(x))
    return t.time() - t0
"""
    assert len(_by_check(_lint(src2), "sync-timing")) == 1


def test_sync_timing_pairs_block_in_nested_def():
    """A closure blocking inside a clock-reading function is the same
    timed region — nested-def records propagate to the parent frame."""
    src = """
import time, jax

def bench_step(fn, x):
    def run():
        return jax.block_until_ready(fn(x))
    t0 = time.perf_counter()
    out = run()
    return time.perf_counter() - t0
"""
    assert len(_by_check(_lint(src), "sync-timing")) == 1


def test_sync_timing_clean_correctness_sync():
    """block_until_ready with NO clock in scope is a correctness sync,
    not a timing bug — must not be flagged."""
    src = """
import jax

def settle(out):
    jax.block_until_ready(out)
    return out
"""
    assert not _lint(src)


def test_sync_timing_clean_across_sibling_functions():
    """A clock in one top-level function must not pair with a
    correctness sync in an unrelated sibling."""
    src = """
import time, jax

def now():
    return time.perf_counter()

def settle(out):
    jax.block_until_ready(out)
    return out
"""
    assert not _lint(src)


def test_sync_timing_clean_via_helper():
    """The idiomatic corrected pattern: timing helper, no bare block."""
    src = """
import time
from apex_tpu.runtime import timing

def bench_step(fn, x):
    t0 = time.perf_counter()
    out = fn(x)
    timing.sync(out)
    return time.perf_counter() - t0
"""
    assert not _lint(src)


# ------------------------------------------------------------ host-in-jit

def test_host_pull_in_jit_flagged():
    src = """
import jax
import numpy as np

@jax.jit
def step(x):
    lr = float(x.mean())
    host = np.asarray(x)
    v = x.item()
    return x * lr
"""
    found = _by_check(_lint(src), "host-in-jit")
    assert len(found) == 3
    assert {f.line for f in found} == {7, 8, 9}
    assert all(f.symbol == "step" for f in found)


def test_host_pull_partial_jit_decorator_flagged():
    src = """
import functools, jax

@functools.partial(jax.jit, donate_argnums=(0,))
def step(x):
    return x * float(x.sum())
"""
    assert len(_by_check(_lint(src), "host-in-jit")) == 1


def test_host_pull_clean_outside_jit():
    """float()/np.asarray in host-side code is idiomatic (bench.py's
    launcher, metric emission) — only jit bodies are flagged."""
    src = """
import numpy as np

def emit(metrics, loss):
    metrics["loss"] = float(loss)
    return np.asarray(loss)
"""
    assert not _lint(src)


def test_host_pull_clean_static_shape_arithmetic():
    """int()/float() over trace-time-static metadata is idiomatic jax,
    not a host pull."""
    src = """
import jax

@jax.jit
def step(x, xs):
    n = int(x.shape[0] * 2)
    frac = float(len(xs)) / x.ndim
    return x.reshape(n // 2, -1) * frac
"""
    assert not _lint(src)


def test_host_pull_mixed_traced_static_still_flagged():
    """One static leaf must not exempt a traced pull: x.mean()/x.shape[0]
    concretizes the traced mean."""
    src = """
import jax

@jax.jit
def step(x):
    lr = float(x.mean() / x.shape[0])
    return x * lr
"""
    assert len(_by_check(_lint(src), "host-in-jit")) == 1


def test_dotted_import_binds_root_name():
    """`import numpy.random` binds `numpy`; numpy.asarray in jit is a
    host pull, NOT an rng finding."""
    src = """
import jax
import numpy.random

@jax.jit
def step(x):
    return numpy.asarray(x)
"""
    found = _lint(src)
    assert len(_by_check(found, "host-in-jit")) == 1
    assert not _by_check(found, "rng-in-jit")


def test_host_pull_clean_jnp_in_jit():
    src = """
import jax
import jax.numpy as jnp

@jax.jit
def step(x):
    return jnp.asarray(x, jnp.float32) * jnp.float32(2)
"""
    assert not _lint(src)


# ------------------------------------------------------------- rng-in-jit

def test_python_rng_in_jit_flagged():
    src = """
import jax, random
import numpy as np

@jax.jit
def step(x):
    noise = np.random.normal(size=(4,))
    jitter = random.random()
    return x + noise * jitter
"""
    found = _by_check(_lint(src), "rng-in-jit")
    assert len(found) == 2
    assert {f.line for f in found} == {7, 8}


def test_rng_clean_jax_random_in_jit():
    src = """
import jax

@jax.jit
def step(x, key):
    noise = jax.random.normal(key, x.shape)
    return x + noise
"""
    assert not _lint(src)


def test_rng_clean_from_jax_import_random():
    """`from jax import random` must resolve through the import map and
    not be mistaken for the stdlib random module."""
    src = """
import jax
from jax import random

@jax.jit
def step(x, key):
    return x + random.normal(key, x.shape)
"""
    assert not _lint(src)


def test_rng_aliased_stdlib_random_still_flagged():
    src = """
import jax
import random as rnd

@jax.jit
def step(x):
    return x * rnd.random()
"""
    assert len(_by_check(_lint(src), "rng-in-jit")) == 1


def test_rng_clean_numpy_rng_outside_jit():
    """Host-side data pipelines use np.random legitimately (e.g.
    examples/imagenet_resnet50.py input synthesis)."""
    src = """
import numpy as np

def make_batch(seed):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(8, 8))
"""
    assert not _lint(src)


# -------------------------------------------------------- mutable-default

def test_mutable_default_flagged():
    src = """
def accumulate(x, history=[], opts={}):
    history.append(x)
    return history, opts
"""
    found = _by_check(_lint(src), "mutable-default")
    assert len(found) == 2
    assert all(f.symbol == "accumulate" for f in found)


def test_mutable_default_clean():
    src = """
def accumulate(x, history=None, n=3, name="adam"):
    history = [] if history is None else history
    history.append(x)
    return history
"""
    assert not _lint(src)


# --------------------------------------------------------------- raw-clock

_CLOCK_SRC = """
import time

def measure(fn, x):
    t0 = time.perf_counter()
    out = fn(x)
    return time.perf_counter() - t0
"""


def test_raw_clock_flagged_in_library_code():
    found = _by_check(
        lint_source(_CLOCK_SRC, "apex_tpu/models/llama.py"), "raw-clock")
    assert len(found) == 2
    assert "runtime.timing" in found[0].message


def test_raw_clock_aliased_import_still_flagged():
    src = """
from time import perf_counter as clock

def measure():
    return clock()
"""
    assert _by_check(lint_source(src, "apex_tpu/mlp.py"), "raw-clock")


def test_raw_clock_not_applied_outside_apex_tpu():
    """Driver code (bench.py, tools/, examples/, tests) may read
    clocks; sync-timing still polices HOW it times."""
    for path in ("bench.py", "tools/tpu_profile.py",
                 "examples/llama_train.py", "snippet.py"):
        assert not _by_check(lint_source(_CLOCK_SRC, path), "raw-clock")


def test_raw_clock_allowlists_sanctioned_clock_owners():
    for path in ("apex_tpu/runtime/timing.py",
                 "apex_tpu/observability/registry.py",
                 "apex_tpu/observability/recompile.py",
                 # retry backoff/deadlines are host wall-time by design
                 "apex_tpu/resilience/retry.py"):
        assert not _by_check(lint_source(_CLOCK_SRC, path), "raw-clock")


def test_raw_clock_gate_uses_abspath_not_cwd_relative_relpath():
    """Linting from inside the package (relpath 'amp/scaler.py') must
    still recognize library code via the absolute path — and the
    allowlist must match from the LAST apex_tpu segment."""
    found = _by_check(
        lint_source(_CLOCK_SRC, "amp/scaler.py",
                    abspath="/ckpt/apex_tpu/amp/scaler.py"), "raw-clock")
    assert found
    assert not _by_check(
        lint_source(_CLOCK_SRC, "timing.py",
                    abspath="/ckpt/apex_tpu/runtime/timing.py"),
        "raw-clock")


def test_raw_clock_suppressible():
    src = """
import time

def measure():
    return time.monotonic()  # apex-lint: disable=raw-clock
"""
    assert not _by_check(
        lint_source(src, "apex_tpu/models/gpt2.py"), "raw-clock")


def test_raw_clock_clean_tree():
    """The live apex_tpu tree must carry no raw clocks outside the
    allowlist — the satellite's point: every timer in the library goes
    through the corrected-sync machinery."""
    import os

    from apex_tpu.analysis.ast_checks import lint_paths

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    found = [f for f in lint_paths([os.path.join(repo, "apex_tpu")],
                                   root=repo, checks=("raw-clock",))
             if f.check == "raw-clock"]
    assert not found, "\n".join(f.render() for f in found)


# ------------------------------------------------- suppression + baseline

def test_suppression_on_line_and_line_above():
    src = """
import time, jax

def bench(fn, x):
    t0 = time.perf_counter()
    out = fn(x)
    jax.block_until_ready(out)  # apex-lint: disable=sync-timing
    # apex-lint: disable=sync-timing
    jax.block_until_ready(out)
    return time.perf_counter() - t0
"""
    assert not _lint(src)


def test_trailing_suppression_does_not_blanket_next_line():
    """A trailing comment suppresses ITS line only; the same violation
    unannotated on the next line must still be flagged."""
    src = """
import time, jax

def bench(fn, x):
    t0 = time.perf_counter()
    jax.block_until_ready(fn(x))  # apex-lint: disable=sync-timing
    jax.block_until_ready(fn(x))
    return time.perf_counter() - t0
"""
    found = _by_check(_lint(src), "sync-timing")
    assert len(found) == 1 and found[0].line == 7


def test_suppression_is_check_specific():
    src = """
import jax

@jax.jit
def step(x):
    return x * float(x.sum())  # apex-lint: disable=rng-in-jit
"""
    assert len(_by_check(_lint(src), "host-in-jit")) == 1


def test_bare_suppression_disables_all():
    src = """
import jax

@jax.jit
def step(x):
    return x * float(x.sum())  # apex-lint: disable
"""
    assert not _lint(src)


def test_unknown_check_id_raises():
    with pytest.raises(ValueError, match="unknown AST check"):
        lint_source("x = 1", "s.py", checks=("bogus",))


def test_baseline_roundtrip_and_multiplicity(tmp_path):
    f1 = Finding("sync-timing", "error", "a.py", 3, "f", "m1")
    f2 = Finding("sync-timing", "error", "a.py", 9, "f", "m2")  # same key
    f3 = Finding("host-in-jit", "error", "b.py", 1, "g", "m3")
    path = tmp_path / "baseline.json"
    save_baseline(path, [f1, f2])
    baseline = load_baseline(path)
    assert baseline == collections.Counter({f1.key: 2})
    # both grandfathered slots consumed; the third finding is new
    assert new_findings([f1, f2, f3], baseline) == [f3]
    # a THIRD occurrence of the same key no longer fits the budget
    assert new_findings([f1, f2, f1], baseline) == [f1]


# ------------------------------------ swallowed-exception-in-step-loop

_SWALLOW = "swallowed-exception-in-step-loop"


def test_swallowed_exception_in_for_loop_flagged():
    src = """
def train(steps):
    for step in range(steps):
        try:
            run_step(step)
        except Exception:
            continue
"""
    found = _by_check(lint_source(src, "apex_tpu/train.py"), _SWALLOW)
    assert len(found) == 1
    assert found[0].line == 6 and found[0].symbol == "train"
    assert "retry.Policy" in found[0].message


def test_swallowed_bare_except_pass_in_while_flagged_in_examples():
    src = """
while True:
    try:
        step()
    except:
        pass
"""
    found = _by_check(lint_source(src, "examples/train.py"), _SWALLOW)
    assert len(found) == 1 and found[0].symbol == "<module>"


def test_swallowed_broad_class_in_tuple_flagged():
    src = """
def loop(xs):
    for x in xs:
        try:
            f(x)
        except (ValueError, Exception):
            pass
"""
    assert _by_check(lint_source(src, "apex_tpu/a.py"), _SWALLOW)


def test_narrow_class_or_handled_body_not_flagged():
    src = """
def loop(xs, log):
    for x in xs:
        try:
            f(x)
        except ValueError:
            continue
        try:
            g(x)
        except Exception as e:
            log(e)
            continue
"""
    assert not _by_check(lint_source(src, "apex_tpu/a.py"), _SWALLOW)


def test_swallow_outside_loop_not_flagged():
    src = """
def probe():
    try:
        f()
    except Exception:
        pass
"""
    assert not _by_check(lint_source(src, "apex_tpu/a.py"), _SWALLOW)


def test_swallow_in_nested_def_inside_loop_not_flagged():
    """A handler in a function *defined* in a loop body is not
    per-iteration control flow — depth resets at the def boundary."""
    src = """
def outer(xs):
    for x in xs:
        def cb():
            try:
                f()
            except Exception:
                pass
        register(cb)
"""
    assert not _by_check(lint_source(src, "apex_tpu/a.py"), _SWALLOW)


def test_swallow_not_applied_outside_apex_tpu_and_examples():
    src = """
for x in xs:
    try:
        f(x)
    except Exception:
        continue
"""
    for path in ("bench.py", "tools/tpu_profile.py", "snippet.py"):
        assert not _by_check(lint_source(src, path), _SWALLOW)
    assert _by_check(lint_source(src, "train.py",
                                 abspath="/ck/apex_tpu/train.py"),
                     _SWALLOW)


def test_swallow_suppressible():
    src = """
for x in xs:
    try:
        f(x)
    except Exception:  # apex-lint: disable=swallowed-exception-in-step-loop
        pass
"""
    assert not _by_check(lint_source(src, "apex_tpu/a.py"), _SWALLOW)


# ---------------------------------------------------- hardcoded-tile-size

_TILE = "hardcoded-tile-size"

_TILE_DIRECT_SRC = """
from jax.experimental import pallas as pl

def build(h):
    row = pl.BlockSpec((512, 1024), lambda i: (i, 0))
    sc = pl.BlockSpec((1, 4), lambda i: (0, 0))
    var = pl.BlockSpec((h, 1), lambda i: (i, 0))
    return row, sc, var
"""


def test_tile_literal_in_blockspec_flagged():
    found = _by_check(_lint(_TILE_DIRECT_SRC), _TILE)
    # 512 and 1024 are tile-sized; the (1, 4) scalar block and the
    # variable/singleton dims are layout plumbing, not tunable tiles
    assert len(found) == 2
    assert "apex_tpu.tuning" in found[0].message


def test_tile_blockspec_kwarg_form_flagged():
    src = """
import jax.experimental.pallas as pl
s = pl.BlockSpec(block_shape=(256, 128), index_map=lambda i: (i, 0))
"""
    assert len(_by_check(_lint(src), _TILE)) == 2


def test_tile_module_constant_flagged_only_with_blockspec():
    src_const = """
from jax.experimental import pallas as pl
_BLOCK_ROWS = 512
_COLS = 1024
_BLOCKED_BK = 2048

def f(block, h):
    return pl.BlockSpec((block, h), lambda i: (i, 0))
"""
    found = _by_check(_lint(src_const), _TILE)
    assert {f.line for f in found} == {3, 4, 5}
    # the same constants in a file with no BlockSpec are not kernel
    # geometry (e.g. a data loader's _TILE_ROWS)
    src_nospec = "_BLOCK_ROWS = 512\n_COLS = 1024\n"
    assert not _by_check(_lint(src_nospec), _TILE)
    # non-tile names and sub-tile values stay quiet
    src_clean = """
from jax.experimental import pallas as pl
_VMEM_ROW_BUDGET = 2 * 1024 * 1024
_WHOLE_ROW_MAX_SK = 16384
_SCALARS = 4

def f(block, h):
    return pl.BlockSpec((block, h), lambda i: (i, 0))
"""
    assert not _by_check(_lint(src_clean), _TILE)


def test_tile_allowlisted_modules():
    """pallas_config and the tuner's search-space tables are the two
    sanctioned homes for tile numbers."""
    for path in ("apex_tpu/ops/pallas_config.py",
                 "apex_tpu/tuning/search_space.py"):
        assert not _by_check(
            lint_source(_TILE_DIRECT_SRC, path, abspath="/r/" + path),
            _TILE)
    assert _by_check(
        lint_source(_TILE_DIRECT_SRC, "apex_tpu/ops/layer_norm.py",
                    abspath="/r/apex_tpu/ops/layer_norm.py"), _TILE)


def test_tile_suppressible():
    src = """
from jax.experimental import pallas as pl
s = pl.BlockSpec((8, 128), lambda i: (0, 0))  # apex-lint: disable=hardcoded-tile-size
"""
    assert not _by_check(_lint(src), _TILE)


def test_tile_clean_tree():
    """The live tree is at 0 findings: every former offender
    (fused_adam_kernel's slab constants, layer_norm's _BLOCK_ROWS,
    fused_softmax's _BLOCKED_BK) is routed through apex_tpu.tuning."""
    import os

    from apex_tpu.analysis.ast_checks import lint_paths

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    found = [f for f in lint_paths(
        [os.path.join(repo, "apex_tpu"), os.path.join(repo, "bench.py")],
        root=repo, checks=(_TILE,)) if f.check == _TILE]
    assert not found, "\n".join(f.render() for f in found)


# --------------------------------------------------------- unclosed-span

_UNCLOSED = "unclosed-span"


def test_unclosed_span_assignment_flagged():
    src = """
from apex_tpu.observability import span

def hot_path():
    ctx = span("pp/forward")
    ctx.__enter__()
"""
    found = _by_check(lint_source(src, "apex_tpu/a.py",
                                  abspath="/r/apex_tpu/a.py"), _UNCLOSED)
    assert len(found) == 1
    assert found[0].line == 5
    assert "with" in found[0].message


def test_unclosed_span_bare_statement_flagged():
    """A span() whose CM is simply dropped never closes at all."""
    src = """
from apex_tpu.observability.profiling.spans import span

def f():
    span("lost")
"""
    assert len(_by_check(lint_source(
        src, "apex_tpu/a.py", abspath="/r/apex_tpu/a.py"),
        _UNCLOSED)) == 1


def test_unclosed_scope_and_attribute_form_flagged():
    """The legacy scope() helper and the obs.span attribute form are
    policed identically."""
    src = """
from apex_tpu import observability as obs
from apex_tpu.observability import scope

def f():
    cm = scope("timer/x")
    cm2 = obs.span("step")
    return cm, cm2
"""
    found = _by_check(lint_source(src, "apex_tpu/a.py",
                                  abspath="/r/apex_tpu/a.py"), _UNCLOSED)
    assert {f.line for f in found} == {6, 7}


def test_with_and_enter_context_forms_clean():
    src = """
import contextlib

from apex_tpu.observability import span, scope

def f():
    with span("outer"), scope("inner"):
        pass
    with contextlib.ExitStack() as st:
        st.enter_context(span("stacked"))
"""
    assert not _by_check(lint_source(
        src, "apex_tpu/a.py", abspath="/r/apex_tpu/a.py"), _UNCLOSED)


def test_local_span_helper_not_flagged():
    """A local function that happens to be named span is not a tracer
    span — the name must resolve into the observability package."""
    src = """
def span(n):
    return n

def f():
    return span("just a string")
"""
    assert not _by_check(lint_source(
        src, "apex_tpu/a.py", abspath="/r/apex_tpu/a.py"), _UNCLOSED)


def test_unclosed_span_scoped_to_library_and_examples():
    src = """
from apex_tpu.observability import span
ctx = span("x")
"""
    assert _by_check(lint_source(src, "examples/a.py",
                                 abspath="/r/examples/a.py"), _UNCLOSED)
    # driver plumbing (tools/, bench.py) is out of scope
    assert not _by_check(lint_source(src, "tools/a.py",
                                     abspath="/r/tools/a.py"), _UNCLOSED)


def test_unclosed_span_suppressible():
    src = """
from apex_tpu.observability import span

class Managed:
    def __enter__(self):
        self._cm = span("managed")  # apex-lint: disable=unclosed-span
        return self._cm.__enter__()
"""
    assert not _by_check(lint_source(
        src, "apex_tpu/a.py", abspath="/r/apex_tpu/a.py"), _UNCLOSED)


def test_unclosed_span_clean_tree():
    """The live tree is at 0 findings: every hot-path span (pp/tp/ddp/
    fused-adam), the pyprof shim and the registry Timer are either
    with-form or carry a justified suppression."""
    import os

    from apex_tpu.analysis.ast_checks import lint_paths

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    found = [f for f in lint_paths(
        [os.path.join(repo, "apex_tpu"), os.path.join(repo, "examples")],
        root=repo, checks=(_UNCLOSED,)) if f.check == _UNCLOSED]
    assert not found, "\n".join(f.render() for f in found)


# ------------------------------------------- host-isnan-in-step-loop

_ISNAN = "host-isnan-in-step-loop"


def test_host_isnan_bool_pull_in_loop_flagged():
    """Seeded regression 1: the classic per-step poll — bool() on a
    jnp.isnan reduction inside the step loop."""
    src = """
import jax.numpy as jnp

def train(step_fn, state, n):
    for it in range(n):
        state, loss = step_fn(state, it)
        if bool(jnp.isnan(loss).any()):
            break
"""
    found = _by_check(lint_source(src, "apex_tpu/train.py",
                                  abspath="/r/apex_tpu/train.py"),
                      _ISNAN)
    assert len(found) == 1 and found[0].line == 7
    assert "observability.numerics" in found[0].message


def test_host_isnan_item_and_condition_pulls_flagged():
    """Seeded regression 2: .item() pulls and bare `if jnp.isinf(...)`
    conditions (an implicit bool()) inside loops — one finding per
    pull site, nested wrappers never double-count."""
    src = """
import jax.numpy as jnp

def watch(tensors):
    while True:
        for t in tensors:
            if jnp.isinf(t).any().item():
                return t
        bad = float(jnp.isnan(tensors[0]).sum())
"""
    found = _by_check(lint_source(src, "examples/watch.py",
                                  abspath="/r/examples/watch.py"),
                      _ISNAN)
    assert sorted(f.line for f in found) == [7, 9]


def test_host_isnan_clean_and_exempt_cases():
    # host floats (math/np), on-device isnan use, and out-of-loop
    # pulls are all idiomatic — no findings
    clean = """
import math
import numpy as np
import jax.numpy as jnp

def train(step_fn, state, n):
    for it in range(n):
        state, loss_f = step_fn(state, it)
        if math.isnan(loss_f) or np.isnan(loss_f):
            break
        state = jnp.where(jnp.isnan(state), 0.0, state)

def once(x):
    return bool(jnp.isnan(x).any())
"""
    assert not _by_check(lint_source(clean, "apex_tpu/train.py",
                                     abspath="/r/apex_tpu/train.py"),
                         _ISNAN)
    # the numerics package is the sanctioned implementation: exempt
    flagged = """
import jax.numpy as jnp

def pull(leaves):
    for leaf in leaves:
        if bool(jnp.isnan(leaf).any()):
            return leaf
"""
    assert not _by_check(lint_source(
        flagged, "apex_tpu/observability/numerics/stats.py",
        abspath="/r/apex_tpu/observability/numerics/stats.py"),
        _ISNAN)
    # driver code (tools/, bench.py) is out of scope, like the other
    # step-loop checks
    assert not _by_check(lint_source(flagged, "tools/probe.py",
                                     abspath="/r/tools/probe.py"),
                         _ISNAN)


def test_host_isnan_suppressible_and_repo_clean():
    src = """
import jax.numpy as jnp

def train(xs):
    for x in xs:
        if bool(jnp.isnan(x).any()):  # apex-lint: disable=host-isnan-in-step-loop
            break
"""
    assert not _by_check(lint_source(src, "apex_tpu/a.py",
                                     abspath="/r/apex_tpu/a.py"),
                         _ISNAN)
    import os

    from apex_tpu.analysis.ast_checks import lint_paths

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    found = [f for f in lint_paths(
        [os.path.join(repo, "apex_tpu"), os.path.join(repo, "examples")],
        root=repo, checks=(_ISNAN,)) if f.check == _ISNAN]
    assert not found, "\n".join(f.render() for f in found)


# --------------------------------------- rank-unsafe-artifact-path

_RANK = "rank-unsafe-artifact-path"


def test_rank_unsafe_fixed_artifact_open_flagged():
    src = """
import os, json

def dump(records, directory):
    with open(os.path.join(directory, "metrics.jsonl"), "w") as f:
        for rec in records:
            f.write(json.dumps(rec) + "\\n")
"""
    found = _by_check(lint_source(src, "apex_tpu/telemetry.py",
                                  abspath="/r/apex_tpu/telemetry.py"),
                      _RANK)
    assert len(found) == 1
    assert "metrics.jsonl" in found[0].message
    assert "rank_path" in found[0].message
    # append mode is the interleave variant of the same race
    src_a = src.replace('"w"', '"a"')
    assert _by_check(lint_source(src_a, "apex_tpu/telemetry.py",
                                 abspath="/r/apex_tpu/telemetry.py"),
                     _RANK)


def test_rank_unsafe_clean_forms_pass():
    src = """
import os
from apex_tpu.observability.fleet import rank_path

def dump(directory, rank, path):
    # a rank component in an f-string literal
    with open(os.path.join(directory, f"m.rank{rank}.jsonl"), "w") as f:
        f.write("x")
    # routed through the sanctioned helper
    with open(rank_path(os.path.join(directory, "m.jsonl")), "w") as f:
        f.write("x")
    # read-mode is not a write race
    with open(os.path.join(directory, "m.jsonl")) as f:
        f.read()
    # a variable path is the caller's responsibility at its own site
    with open(path, "w") as f:
        f.write("x")
    # pid-qualified names are per-process already
    with open(os.path.join(directory, f"log_{os.getpid()}.json"),
              "w") as f:
        f.write("x")
"""
    assert not _by_check(lint_source(src, "apex_tpu/telemetry.py",
                                     abspath="/r/apex_tpu/telemetry.py"),
                         _RANK)


def test_rank_unsafe_scoped_and_exempt():
    src = """
def dump(directory):
    import os
    with open(os.path.join(directory, "stats.json"), "w") as f:
        f.write("x")
"""
    # driver code (tools/, bench.py) is out of scope
    assert not _by_check(lint_source(src, "tools/report.py",
                                     abspath="/r/tools/report.py"),
                         _RANK)
    # the fleet identity package IS the sanctioned implementation
    assert not _by_check(lint_source(
        src, "apex_tpu/observability/fleet/identity.py",
        abspath="/r/apex_tpu/observability/fleet/identity.py"), _RANK)
    # examples run inside multiproc workers: in scope
    assert _by_check(lint_source(src, "examples/train.py",
                                 abspath="/r/examples/train.py"),
                     _RANK)


def test_rank_unsafe_suppressible_and_repo_clean():
    src = """
import os

def dump(directory):
    with open(os.path.join(directory, "one_writer_only.json"), "w") as f:  # apex-lint: disable=rank-unsafe-artifact-path
        f.write("x")
"""
    assert not _by_check(lint_source(src, "apex_tpu/a.py",
                                     abspath="/r/apex_tpu/a.py"),
                         _RANK)
    import os

    from apex_tpu.analysis.ast_checks import lint_paths

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    found = [f for f in lint_paths(
        [os.path.join(repo, "apex_tpu"), os.path.join(repo, "examples")],
        root=repo, checks=(_RANK,)) if f.check == _RANK]
    assert not found, "\n".join(f.render() for f in found)


# --------------------------------------- raw-memory-introspection

_MEMINTRO = "raw-memory-introspection"


def test_memory_introspection_live_arrays_in_loop_flagged():
    """Seeded regression 1 (ISSUE 15): the ad-hoc live-bytes poll — a
    jax.live_arrays() sweep inside the step loop, the memory analog of
    the per-tensor isnan pull."""
    src = """
import jax

def train(step_fn, state, n):
    for it in range(n):
        state, _ = step_fn(state, it)
        used = sum(a.nbytes for a in jax.live_arrays())
"""
    found = _by_check(lint_source(src, "apex_tpu/train.py",
                                  abspath="/r/apex_tpu/train.py"),
                      _MEMINTRO)
    assert len(found) == 1 and found[0].line == 7
    assert "observability.memory" in found[0].message


def test_memory_introspection_stats_and_profile_flagged():
    """Seeded regression 2: a direct .memory_stats() read (subscripted
    device base — no resolvable dotted chain) and a
    jax.profiler.device_memory_profile() call, each its own finding.
    from-imports resolve through the module's import map."""
    src = """
import jax

def report():
    stats = jax.devices()[0].memory_stats()
    prof = jax.profiler.device_memory_profile()
"""
    found = _by_check(lint_source(src, "examples/report.py",
                                  abspath="/r/examples/report.py"),
                      _MEMINTRO)
    assert sorted(f.line for f in found) == [5, 6]
    # .live_executables() on a stashed client: attribute-matched too
    # (its receiver breaks the dotted chain exactly like memory_stats)
    src_exec = """
import jax

def sweep():
    client = jax.devices()[0].client
    return client.live_executables()
"""
    assert _by_check(lint_source(src_exec, "apex_tpu/runtime/s.py",
                                 abspath="/r/apex_tpu/runtime/s.py"),
                     _MEMINTRO)
    src2 = """
from jax import live_arrays

def f():
    return live_arrays()
"""
    assert _by_check(lint_source(src2, "examples/f.py",
                                 abspath="/r/examples/f.py"),
                     _MEMINTRO)


def test_memory_introspection_clean_and_exempt_cases():
    # a LOCAL helper named live_arrays is not jax's; monitor-routed
    # reads are the sanctioned shape
    clean = """
from apex_tpu.observability.memory import MemoryMonitor, memory_snapshot

def live_arrays():
    return []

def train(n):
    mon = MemoryMonitor("t", every=8)
    for it in range(n):
        mon.observe(it)
        xs = live_arrays()
"""
    assert not _by_check(lint_source(clean, "apex_tpu/train.py",
                                     abspath="/r/apex_tpu/train.py"),
                         _MEMINTRO)
    flagged = """
import jax

def walk():
    stats = jax.devices()[0].memory_stats()
    return jax.live_arrays()
"""
    # the memory package + pallas_config ARE the sanctioned owners
    assert not _by_check(lint_source(
        flagged, "apex_tpu/observability/memory/hbm.py",
        abspath="/r/apex_tpu/observability/memory/hbm.py"), _MEMINTRO)
    assert not _by_check(lint_source(
        flagged, "apex_tpu/ops/pallas_config.py",
        abspath="/r/apex_tpu/ops/pallas_config.py"), _MEMINTRO)
    # driver code (tools/, bench.py) is out of scope like the other
    # step-loop checks
    assert not _by_check(lint_source(flagged, "tools/probe.py",
                                     abspath="/r/tools/probe.py"),
                         _MEMINTRO)


def test_memory_introspection_suppressible_and_repo_clean():
    src = """
import jax

def f():
    return jax.live_arrays()  # apex-lint: disable=raw-memory-introspection
"""
    assert not _by_check(lint_source(src, "apex_tpu/a.py",
                                     abspath="/r/apex_tpu/a.py"),
                         _MEMINTRO)
    import os

    from apex_tpu.analysis.ast_checks import lint_paths

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    found = [f for f in lint_paths(
        [os.path.join(repo, "apex_tpu"), os.path.join(repo, "examples")],
        root=repo, checks=(_MEMINTRO,)) if f.check == _MEMINTRO]
    assert not found, "\n".join(f.render() for f in found)
