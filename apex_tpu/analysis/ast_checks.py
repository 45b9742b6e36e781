"""Engine 2: AST-level lint for host-sync and trace-hygiene anti-patterns.

Runs over apex_tpu's own sources, ``examples/``, ``tools/`` and
``bench.py`` — the code that *drives* TPUs, where a timing that does
not wait for the device reports dispatch time as device time. Checks:

- ``sync-timing``     ``block_until_ready`` inside a function that also
                      reads a wall clock: a hand-rolled timed region.
                      Dispatch is asynchronous, so a clock interval is
                      device time only around a blocking call on every
                      output; that pairing lives in ONE place,
                      ``apex_tpu.runtime.timing`` (``sync``/``time_fn``).
- ``host-in-jit``     ``float()``/``int()``/``np.asarray``/``.item()``/
                      ``.tolist()`` inside a jit-decorated body: host
                      pulls that either fail to trace or silently sync.
- ``rng-in-jit``      Python/numpy RNG inside a jit-decorated body: the
                      sample is baked in at trace time, identical every
                      step. Use ``jax.random`` with a threaded key.
- ``mutable-default`` mutable default argument (list/dict/set): shared
                      across calls; with jit in play, also a cache-key
                      footgun.
- ``raw-clock``       a direct wall-clock read (``time.perf_counter`` &
                      co) in library code under ``apex_tpu/`` outside
                      ``runtime/timing.py`` and ``observability/``: all
                      timing must flow through the runtime.timing
                      helpers / the observability Timer, or the next
                      hand-rolled timer measures dispatch, not the
                      device. Driver code (bench.py, tools/,
                      examples/) may read clocks — sync-timing still
                      polices HOW it times.
- ``swallowed-exception-in-step-loop``
                      ``except Exception/BaseException/bare: pass`` (or
                      ``continue``) inside a ``for``/``while`` body in
                      ``apex_tpu/`` or ``examples/``: a step loop that
                      silently eats per-iteration failures hides NaN
                      storms, torn checkpoint writes and dying
                      collectives until the run is unrecoverable.
                      Resilience must be explicit — retry transient
                      classes via ``apex_tpu.resilience.retry.Policy``,
                      or at least count/log before continuing.
- ``unclosed-span``   an ``apex_tpu.observability`` ``span(...)``/
                      ``scope(...)`` call in ``apex_tpu/`` or
                      ``examples/`` that is not the context expression
                      of a ``with`` (or an ``ExitStack.enter_context``
                      argument): a span opened without its guaranteed
                      close leaks an entry on the tracer's open-span
                      stack forever — the flight recorder then reports
                      a phantom in-flight region on every dump, nesting
                      depths of later spans are wrong, and the paired
                      profiler TraceAnnotation never pops. Manual
                      ``__enter__``/``__exit__`` pairing inside another
                      context manager's protocol is the one sanctioned
                      shape (suppress with a justification).
- ``host-isnan-in-step-loop``
                      a ``jnp.isnan``/``jnp.isinf`` result pulled to
                      host (``bool()``/``float()``/``.item()``/
                      ``.tolist()``, or used directly as an ``if``
                      condition) lexically inside a ``for``/``while``
                      body in ``apex_tpu/`` or ``examples/``: each
                      pull is a device round-trip PER TENSOR PER STEP
                      that serializes the dispatch pipeline — the
                      exact anti-pattern the numerics tier exists to
                      replace. Route finiteness checks through
                      ``apex_tpu.observability.numerics`` (one fused
                      on-device reduction for the whole tree, host
                      pull decimated to every N steps); the numerics
                      module itself is exempt — it IS the sanctioned
                      implementation.
- ``rank-unsafe-artifact-path``
                      a write-mode ``open()`` in ``apex_tpu/`` or
                      ``examples/`` (code that runs inside
                      multiproc-launched workers) whose path
                      expression bakes in a fixed artifact filename
                      (a string literal ending in .json/.jsonl/.csv/
                      .log/...) with no rank component anywhere in the
                      expression: two ranks handed the same path
                      interleave or clobber each other's telemetry —
                      the ISSUE 12 failure mode that raced every
                      ``APEX_TPU_METRICS`` dump. Route shared paths
                      through ``observability.fleet.rank_path`` (or
                      build the name from the rank/pid). A path that
                      arrives as a variable is the caller's problem at
                      the caller's site; a literal is this file's.
- ``hardcoded-tile-size``
                      an integer tile constant fed to ``pl.BlockSpec``
                      outside ``ops/pallas_config.py`` and the tuner's
                      search-space tables (``tuning/search_space.py``):
                      a literal >= 8 (tile-sized — sublane multiples
                      start at 8) directly in a block shape, or a
                      module-level ``_BLOCK*``/``_TILE*``/``*_COLS``-
                      style int constant in a file that builds
                      BlockSpecs. The right tile is a per-device,
                      per-shape search result (the fixed flat-adam
                      (rows, 1024) slab lost 3.2x on v5e to the tiling
                      it shipped with) — route geometry through
                      ``apex_tpu.tuning``.

- ``raw-memory-introspection``
                      a direct ``jax.live_arrays()`` /
                      ``jax.profiler.device_memory_profile()`` /
                      ``.memory_stats()`` call in ``apex_tpu/`` or
                      ``examples/`` outside the memory observability
                      package and ``ops/pallas_config.py``: the live
                      walk is a host-side sweep of every buffer (and
                      ``get_backend()`` forces backend init from a
                      telemetry read) — ad-hoc calls in a step loop
                      serialize the pipeline exactly like the
                      per-tensor isnan pulls the numerics tier retired,
                      and their numbers bypass the watermark/top-k
                      accounting the OOM forensics depend on. Route
                      through ``apex_tpu.observability.memory``
                      (``MemoryMonitor`` decimated snapshots,
                      ``device_memory_stats``); ``pallas_config`` owns
                      the ``bytes_limit`` budget read.
- ``nondeterministic-collective-order``
                      a ``for`` loop over an unordered iterable (set
                      literal/comprehension, ``set()``/``frozenset()``
                      or a set-method call, ``os.listdir``) whose body
                      builds buckets or issues collectives, in comms
                      scheduling code (``apex_tpu/parallel/``,
                      ``runtime/``, ``distributed/``): set iteration
                      order differs across processes (string hash
                      randomization) and listdir follows filesystem
                      order, so ranks disagree on bucket layout /
                      collective issue order — the plan_buckets-shaped
                      deadlock seed. Iterate ``sorted(...)``.

Suppress with ``# apex-lint: disable=<id>`` on (or above) the line.
"""

from __future__ import annotations

import ast
import os
import re

from apex_tpu.analysis.findings import Finding, is_suppressed

AST_CHECKS = ("sync-timing", "host-in-jit", "rng-in-jit",
              "mutable-default", "raw-clock",
              "swallowed-exception-in-step-loop",
              "hardcoded-tile-size", "unclosed-span",
              "host-isnan-in-step-loop", "rank-unsafe-artifact-path",
              "raw-fp8-cast", "nondeterministic-collective-order",
              "raw-memory-introspection")

# Modules whose job is the timed-region sync itself.
_SYNC_ALLOWLIST = {os.path.join("apex_tpu", "runtime", "timing.py")}

# raw-clock applies only to library code under apex_tpu/; these own the
# sanctioned clocks (timing.py implements the timed-region sync, the
# observability layer's Timer/StepReporter are built on it;
# resilience/ reads wall time for retry backoff/deadlines — host-side
# scheduling, not device phase timing; serving/ stamps request
# lifecycle times (latency/ttft) and paces loadgen arrivals — same
# host-side scheduling class as resilience/).
_RAW_CLOCK_ALLOW_FILES = {"apex_tpu/runtime/timing.py"}
_RAW_CLOCK_ALLOW_PREFIXES = ("apex_tpu/observability/",
                             "apex_tpu/resilience/",
                             "apex_tpu/serving/")


def _apex_tail(path: str):
    """``path`` from its last ``apex_tpu`` DIRECTORY segment on, or
    None when no such segment exists — the shared scoping idiom for
    library-code checks (absolute paths preferred: relpaths depend on
    the caller's cwd/root; matching from the LAST segment keeps
    checkouts that live under a directory named apex_tpu correct)."""
    norm = path.replace("\\", "/")
    if "apex_tpu" not in norm.split("/")[:-1]:
        return None
    return norm[norm.rindex("apex_tpu/"):]


def _raw_clock_applies(path: str) -> bool:
    """Is ``path`` library code the raw-clock check governs? Library
    code under apex_tpu/, minus the allowlisted clock owners."""
    tail = _apex_tail(path)
    if tail is None or tail in _RAW_CLOCK_ALLOW_FILES:
        return False
    return not any(tail.startswith(p) for p in _RAW_CLOCK_ALLOW_PREFIXES)


def _swallowed_exc_applies(path: str) -> bool:
    """Is ``path`` governed by swallowed-exception-in-step-loop? Library
    code under an ``apex_tpu`` package dir, or anything under an
    ``examples`` dir — the two places step loops live. Driver plumbing
    (bench.py launcher, tools/) may legitimately blanket-continue over
    secondary work."""
    parts = path.replace("\\", "/").split("/")[:-1]
    return "apex_tpu" in parts or "examples" in parts


# unclosed-span polices the same ground as swallowed-exception: the
# library + examples, where instrumented hot paths live. Span/scope
# names must resolve (through the module's imports) into the
# observability package — a local helper that happens to be called
# `span` is not a tracer span.
_SPAN_NAMES = ("span", "scope")


def _unclosed_span_applies(path: str) -> bool:
    return _swallowed_exc_applies(path)


# host-isnan-in-step-loop polices the same ground (library +
# examples step loops), minus the numerics package — it IS the
# sanctioned decimated/fused implementation of these checks.
_ISNAN_EXEMPT_PREFIX = "apex_tpu/observability/numerics/"


def _host_isnan_applies(path: str) -> bool:
    norm = path.replace("\\", "/")
    if _ISNAN_EXEMPT_PREFIX in norm:
        return False
    return _swallowed_exc_applies(path)


_ISNAN_NAMES = frozenset({"isnan", "isinf"})


# rank-unsafe-artifact-path: library + examples code (what
# multiproc-launched workers actually execute). The fleet identity
# module is exempt — it IS the sanctioned suffixing implementation.
_RANK_PATH_EXEMPT_PREFIX = "apex_tpu/observability/fleet/"

# filename extensions that mean "telemetry/artifact write" — a fixed
# one of these inside a worker is the shard-clobber pattern
_ARTIFACT_EXTS = (".json", ".jsonl", ".csv", ".log", ".txt", ".pb",
                  ".tsv")

# an identifier anywhere in the path expression that smells like a
# per-rank/per-process component ("...rank...", pid lookups, the
# sanctioned helper) clears the finding
_RANK_COMPONENT_RE = re.compile(
    r"rank|process_index|getpid|\bpid\b|worker|shard|proc_?id",
    re.IGNORECASE)

_WRITE_MODES = {"w", "a", "wb", "ab", "w+", "a+", "wt", "at", "x",
                "xb"}


def _rank_unsafe_applies(path: str) -> bool:
    norm = path.replace("\\", "/")
    if _RANK_PATH_EXEMPT_PREFIX in norm:
        return False
    return _swallowed_exc_applies(path)


# raw-memory-introspection (ISSUE 15): direct memory-introspection
# calls anywhere but the sanctioned owners — the memory observability
# package (MemoryMonitor's decimated snapshots, the compiled-stats
# capture) and ops/pallas_config.py (the bytes_limit budget read).
_MEMORY_INTROSPECT_EXEMPT_PREFIX = "apex_tpu/observability/memory/"
_MEMORY_INTROSPECT_ALLOW_FILES = {"apex_tpu/ops/pallas_config.py"}

#: function names that ARE memory introspection when they resolve into
#: jax (live_arrays / profiler.device_memory_profile).
_MEMORY_INTROSPECT_JAX_NAMES = frozenset({
    "live_arrays", "device_memory_profile",
})

#: PJRT-object methods matched by ATTRIBUTE name (their receivers —
#: `jax.devices()[0]`, a stashed `client` — break the dotted chain, so
#: jax-root resolution can never see them).
_MEMORY_INTROSPECT_ATTRS = frozenset({
    "memory_stats", "live_executables",
})


def _memory_introspect_applies(path: str) -> bool:
    norm = path.replace("\\", "/")
    if _MEMORY_INTROSPECT_EXEMPT_PREFIX in norm:
        return False
    tail = _apex_tail(path)
    if tail is not None and tail in _MEMORY_INTROSPECT_ALLOW_FILES:
        return False
    return _swallowed_exc_applies(path)


# raw-fp8-cast (ISSUE 13): a bare astype to an fp8 dtype anywhere but
# the sanctioned quantization owners. fp8 casts are only safe behind a
# delayed per-tensor scale + saturation (ops/precision.quantize_fp8 /
# matmul_fp8, fed by the amp Fp8DelayedScaler); a raw cast overflows to
# NaN (E4M3 has no inf encoding) the first time an activation leaves
# ±448. The owners: ops/precision.py (+ its Pallas kernel) and amp/.
_FP8_CAST_ALLOW_FILES = {"apex_tpu/ops/precision.py",
                         "apex_tpu/ops/fp8_cast_kernel.py"}
_FP8_CAST_ALLOW_PREFIXES = ("apex_tpu/amp/",)

# an astype argument that IS an fp8 dtype: jnp/jax.numpy float8_*
# members, the precision module's F8_* aliases (an alias is still a raw
# cast), or a dtype string literal
_FP8_DTYPE_NAME_RE = re.compile(r"^(float8_e4m3fn|float8_e5m2|"
                                r"F8_E4M3|F8_E5M2)$")


def _raw_fp8_applies(path: str) -> bool:
    tail = _apex_tail(path)
    if tail is not None:
        if tail in _FP8_CAST_ALLOW_FILES:
            return False
        if any(tail.startswith(p) for p in _FP8_CAST_ALLOW_PREFIXES):
            return False
    return True


# nondeterministic-collective-order (ISSUE 14): comms scheduling code —
# parallel/ (bucket plans, collective issue chains), runtime/
# (plan_buckets) and the distributed shims. Every rank must build the
# SAME bucket list and issue collectives in the SAME order; a loop over
# a set (hash-randomized for strings across processes) or os.listdir
# (filesystem order) deciding either is a cross-rank deadlock/desync
# seed: rank A packs {f32, bf16} buckets in one order, rank B in the
# other, and the psums pair the wrong buffers.
_NONDET_ORDER_PREFIXES = ("apex_tpu/parallel/", "apex_tpu/runtime/",
                          "apex_tpu/distributed/")

#: loop bodies that "issue comms / build buckets": a collective call, a
#: plan_buckets call, or any bucket-named identifier
_ORDER_COLLECTIVE_NAMES = frozenset({
    "psum", "pmean", "pmax", "pmin", "all_gather", "psum_scatter",
    "reduce_scatter", "all_to_all", "ppermute", "plan_buckets",
})

#: set-producing call tails a for-loop must not iterate unsorted
_SET_CALL_NAMES = frozenset({"set", "frozenset"})
_SET_METHOD_NAMES = frozenset({"difference", "union", "intersection",
                               "symmetric_difference"})


def _nondet_order_applies(path: str) -> bool:
    tail = _apex_tail(path)
    return tail is not None and any(
        tail.startswith(p) for p in _NONDET_ORDER_PREFIXES)


# hardcoded-tile-size: the two modules tile numbers are ALLOWED to live
# in — the dispatch-config defaults and the tuner's search-space tables.
_TILE_SIZE_ALLOW = ("apex_tpu/ops/pallas_config.py",
                    "apex_tpu/tuning/search_space.py")

# Below the fp32 sublane tile (8): a 1-singleton or a tiny scalar-block
# dim (the flat-adam (1, 4) scalar spec) is layout plumbing, not a
# tunable tile.
_TILE_LITERAL_MIN = 8

# Module-constant names that smell like a tile: _BLOCK_ROWS, _BLOCKED_BK,
# _TILE_N, _COLS, BQ/BK... (matched against the upper-cased name).
_TILE_NAME_RE = re.compile(r"(?:^|_)(BLOCK|TILE|COLS|ROWS|BQ|BKV|BK)"
                           r"(?:_|E?D?_|$)")


def _tile_size_applies(path: str) -> bool:
    norm = path.replace("\\", "/")
    return not any(norm.endswith(allow) for allow in _TILE_SIZE_ALLOW)


_BROAD_EXC = {"Exception", "BaseException"}


def _is_broad_handler(type_node) -> bool:
    """Bare ``except:``, ``except Exception``, ``except BaseException``
    — including inside a tuple of classes."""
    if type_node is None:
        return True
    if isinstance(type_node, ast.Tuple):
        return any(_is_broad_handler(e) for e in type_node.elts)
    chain = _attr_chain(type_node)
    return bool(chain) and chain[-1] in _BROAD_EXC


def _body_only_swallows(body) -> bool:
    """True when the handler body does nothing but pass/continue/... —
    no logging, no counter, no re-raise, no fallback value."""
    if not body:
        return True
    for stmt in body:
        if isinstance(stmt, (ast.Pass, ast.Continue)):
            continue
        if isinstance(stmt, ast.Expr) and isinstance(
                stmt.value, ast.Constant) and stmt.value.value is ...:
            continue
        return False
    return True


_CLOCK_CALLS = {("time", "perf_counter"), ("time", "time"),
                ("time", "monotonic"), ("time", "perf_counter_ns"),
                ("timeit", "default_timer")}

_HOST_PULL_NAMES = {"float", "int"}
_HOST_PULL_NP = {"asarray", "array", "copyto"}
_HOST_PULL_METHODS = {"item", "tolist"}


def _attr_chain(node):
    """Dotted name parts of an Attribute/Name chain, outermost first."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return list(reversed(parts))
    return None


_STATIC_ATTRS = {"shape", "ndim", "size", "dtype", "itemsize"}
_STATIC_FNS = {"len", "min", "max", "abs", "int", "float", "round"}


def _is_static_expr(node):
    """True when the WHOLE expression derives from static trace-time
    metadata (``x.shape[0] * 2``, ``len(xs)``): int()/float() on these
    is idiomatic jax, not a host pull. One static leaf is not enough —
    ``x.mean() / x.shape[0]`` still pulls the traced mean."""
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, ast.Attribute):
        return node.attr in _STATIC_ATTRS
    if isinstance(node, ast.Subscript):
        return _is_static_expr(node.value)
    if isinstance(node, ast.Index):  # py<3.9 slice wrapper
        return _is_static_expr(node.value)
    if isinstance(node, ast.BinOp):
        return _is_static_expr(node.left) and _is_static_expr(node.right)
    if isinstance(node, ast.UnaryOp):
        return _is_static_expr(node.operand)
    if isinstance(node, (ast.Tuple, ast.List)):
        return all(_is_static_expr(e) for e in node.elts)
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
        if node.func.id == "len":
            return True  # len() is a host int even on traced arrays
        return (node.func.id in _STATIC_FNS
                and all(_is_static_expr(a) for a in node.args))
    return False


def _is_jit_decorator(dec):
    """jax.jit / jit / pjit, possibly through functools.partial(...)."""
    chain = _attr_chain(dec)
    if chain and chain[-1] in ("jit", "pjit"):
        return True
    if isinstance(dec, ast.Call):
        chain = _attr_chain(dec.func)
        if chain and chain[-1] in ("jit", "pjit"):
            return True
        if chain and chain[-1] == "partial" and dec.args:
            inner = _attr_chain(dec.args[0])
            if inner and inner[-1] in ("jit", "pjit"):
                return True
    return False


class _Visitor(ast.NodeVisitor):
    def __init__(self, path, relpath, checks):
        self.relpath = relpath
        self.checks = checks
        self.findings = []
        # stack of (symbol, in_jit); module scope counts as one frame
        self.stack = [("<module>", False)]
        # per-function-frame call records for sync-timing
        self.frames = [{"clock": [], "block": []}]
        # per-function-frame lexical loop depth (a handler inside a def
        # nested in a loop is NOT per-iteration code — depth resets)
        self.loop_depth = [0]
        # local name -> imported dotted module, so `from jax import
        # random` is not mistaken for the stdlib `random` module
        self.imports = {}
        # hardcoded-tile-size state: module-level tile-named int
        # constants only become findings when the file also builds
        # BlockSpecs (lint_source pairs the two after the walk)
        self.blockspec_seen = False
        self.tile_consts = []  # (lineno, name, value)
        # unclosed-span: Call nodes sanctioned as context-manager uses
        # (a with item's context expression, an enter_context argument)
        # — recorded by the parent before the call itself is visited
        self._cm_calls: set = set()
        # host-isnan-in-step-loop: Call nodes already reported through
        # an enclosing pull (an `if` test, an outer bool()) — one
        # finding per pull site, not one per nested call
        self._isnan_handled: set = set()

    def visit_Import(self, node):
        for alias in node.names:
            if alias.asname:
                self.imports[alias.asname] = alias.name
            else:
                # `import numpy.random` binds the ROOT name `numpy`
                root = alias.name.split(".")[0]
                self.imports[root] = root
        self.generic_visit(node)

    def visit_ImportFrom(self, node):
        if node.module and node.level == 0:
            for alias in node.names:
                self.imports[alias.asname or alias.name] = \
                    f"{node.module}.{alias.name}"
        self.generic_visit(node)

    def _resolve(self, chain):
        """Expand the chain's root through the module's imports:
        ['random','normal'] under `from jax import random` resolves to
        ['jax','random','normal']."""
        root = self.imports.get(chain[0])
        if root is None:
            return chain
        return root.split(".") + chain[1:]

    def _sym(self):
        return self.stack[-1][0]

    def _in_jit(self):
        return self.stack[-1][1]

    def _emit(self, check, severity, line, message):
        if check in self.checks:
            self.findings.append(Finding(
                check, severity, self.relpath, line, self._sym(), message))

    # ------------------------------------------------- function frames

    def _enter_function(self, node):
        jit = self._in_jit() or any(
            _is_jit_decorator(d) for d in getattr(node, "decorator_list",
                                                  ()))
        name = getattr(node, "name", "<lambda>")
        if "mutable-default" in self.checks and hasattr(node, "args"):
            defaults = list(node.args.defaults) + [
                d for d in node.args.kw_defaults if d is not None]
            for d in defaults:
                if isinstance(d, (ast.List, ast.Dict, ast.Set)) or (
                        isinstance(d, ast.Call)
                        and isinstance(d.func, ast.Name)
                        and d.func.id in ("list", "dict", "set")):
                    self.findings.append(Finding(
                        "mutable-default", "warning", self.relpath,
                        d.lineno, name,
                        f"mutable default argument in '{name}': shared "
                        f"across calls (and a jit cache-key footgun); "
                        f"default to None and build inside"))
        self.stack.append((name, jit))
        self.frames.append({"clock": [], "block": []})
        self.loop_depth.append(0)

    def _exit_function(self):
        frame = self.frames.pop()
        if frame["clock"] and frame["block"]:
            for line in frame["block"]:
                self._emit(
                    "sync-timing", "error", line,
                    "block_until_ready in a function that also reads a "
                    "wall clock: a hand-rolled timed region. Dispatch "
                    "is asynchronous, so time only around a blocking "
                    "call on every output — build the region with "
                    "apex_tpu.runtime.timing.sync / time_fn, the one "
                    "place that pairing lives")
        elif len(self.frames) > 1:
            # an unpaired NESTED def usually runs inside its enclosing
            # function's timed region — propagate its records up so a
            # clock in the parent still pairs with a block in a closure.
            # Top-level functions do NOT propagate into the module frame:
            # pairing a clock in one sibling with a block in another
            # would flag unrelated correctness-sync helpers.
            # (Cross-FUNCTION helpers remain out of reach: documented
            # limitation in docs/analysis.md.)
            self.frames[-1]["block"] += frame["block"]
            self.frames[-1]["clock"] += frame["clock"]
        self.stack.pop()
        self.loop_depth.pop()

    def visit_FunctionDef(self, node):
        self._enter_function(node)
        self.generic_visit(node)
        self._exit_function()

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Lambda(self, node):
        self._enter_function(node)
        self.generic_visit(node)
        self._exit_function()

    # ------------------------------------------------- loops / handlers

    def visit_For(self, node):
        if "nondeterministic-collective-order" in self.checks:
            self._check_nondet_order(node)
        self.loop_depth[-1] += 1
        self.generic_visit(node)
        self.loop_depth[-1] -= 1

    visit_AsyncFor = visit_For

    # --------------------------- nondeterministic collective order

    def _nondet_iterable(self, node):
        """A human-readable description when ``node`` (a for-loop's
        iter expression) has no deterministic order: a set
        literal/comprehension, a set()/frozenset()/set-method call, or
        os.listdir. ``sorted(...)`` around any of these never matches
        — that IS the fix."""
        if isinstance(node, (ast.Set, ast.SetComp)):
            return "a set literal"
        if isinstance(node, ast.Call):
            if isinstance(node.func, ast.Name) and \
                    node.func.id in _SET_CALL_NAMES:
                return f"{node.func.id}(...)"
            chain = _attr_chain(node.func)
            if chain:
                if chain[-1] == "listdir":
                    return "os.listdir(...)"
                if chain[-1] in _SET_METHOD_NAMES and len(chain) >= 2:
                    return f".{chain[-1]}(...) (a set)"
        return None

    def _body_issues_comms(self, node) -> bool:
        """Does the loop body contain a collective/plan_buckets call or
        a bucket-named identifier? (the 'this loop decides comms or
        bucket order' signal)."""
        for stmt in node.body:
            for sub in ast.walk(stmt):
                if isinstance(sub, ast.Call):
                    chain = _attr_chain(sub.func)
                    if chain and chain[-1] in _ORDER_COLLECTIVE_NAMES:
                        return True
                if isinstance(sub, ast.Name) and \
                        "bucket" in sub.id.lower():
                    return True
                if isinstance(sub, ast.Attribute) and \
                        "bucket" in sub.attr.lower():
                    return True
        return False

    def _check_nondet_order(self, node):
        how = self._nondet_iterable(node.iter)
        if how is None or not self._body_issues_comms(node):
            return
        self._emit(
            "nondeterministic-collective-order", "error",
            node.iter.lineno,
            f"loop over {how} — an unordered iterable — decides bucket "
            f"construction or collective issue order: set iteration "
            f"order differs across processes (string hash "
            f"randomization) and os.listdir follows filesystem order, "
            f"so two ranks build different bucket lists / issue "
            f"collectives in different orders and the fleet deadlocks "
            f"or pairs the wrong buffers — iterate sorted(...) so "
            f"every rank sees the same order")

    def visit_While(self, node):
        # the While TEST re-evaluates every iteration: an isnan there
        # is a per-step host pull even when the loop itself is
        # top-level
        self._check_isnan_condition(node.test)
        self.loop_depth[-1] += 1
        self.generic_visit(node)
        self.loop_depth[-1] -= 1

    def visit_If(self, node):
        if self.loop_depth[-1] > 0:
            self._check_isnan_condition(node.test)
        self.generic_visit(node)

    # ---------------------------------------------- host isnan pulls

    def _isnan_call_in(self, node):
        """First ``jnp.isnan``/``jnp.isinf`` Call in the subtree (the
        jax one — resolved through the module's imports so a host-side
        ``np.isnan(loss)`` on a Python float never matches)."""
        for sub in ast.walk(node):
            if not isinstance(sub, ast.Call):
                continue
            chain = _attr_chain(sub.func)
            if not chain or chain[-1] not in _ISNAN_NAMES:
                continue
            res = self._resolve(chain)
            if res[0] in ("jax", "jnp"):
                return sub
        return None

    def _emit_isnan_pull(self, container, line, via):
        for sub in ast.walk(container):
            if isinstance(sub, ast.Call):
                self._isnan_handled.add(id(sub))
        self._emit(
            "host-isnan-in-step-loop", "error", line,
            f"jnp.isnan/jnp.isinf result pulled to host ({via}) inside "
            f"a step loop: one device round-trip per tensor per "
            f"iteration, serializing the dispatch pipeline — use "
            f"apex_tpu.observability.numerics (tensor_stats / "
            f"StatsCollector: one fused on-device reduction for the "
            f"whole tree, host pull decimated to every N steps)")

    def _check_isnan_condition(self, test):
        if "host-isnan-in-step-loop" not in self.checks:
            return
        if self._isnan_call_in(test) is not None:
            self._emit_isnan_pull(test, test.lineno,
                                  "used as a branch condition")

    def visit_With(self, node):
        for item in node.items:
            if isinstance(item.context_expr, ast.Call):
                self._cm_calls.add(id(item.context_expr))
        self.generic_visit(node)

    visit_AsyncWith = visit_With

    def visit_Try(self, node):
        if self.loop_depth[-1] > 0:
            for handler in node.handlers:
                if _is_broad_handler(handler.type) and \
                        _body_only_swallows(handler.body):
                    caught = "except:" if handler.type is None else \
                        f"except {ast.unparse(handler.type)}:"
                    self._emit(
                        "swallowed-exception-in-step-loop", "error",
                        handler.lineno,
                        f"'{caught} pass/continue' inside a loop body "
                        f"silently swallows per-step failures (NaN "
                        f"storms, torn checkpoint writes, dying "
                        f"collectives) — retry transient classes via "
                        f"apex_tpu.resilience.retry.Policy, or count/"
                        f"log the failure before continuing")
        self.generic_visit(node)

    visit_TryStar = visit_Try

    # ------------------------------------------------------ call sites

    def visit_Assign(self, node):
        if len(self.stack) == 1 and "hardcoded-tile-size" in self.checks:
            for target in node.targets:
                if isinstance(target, ast.Name) and \
                        _TILE_NAME_RE.search(target.id.upper()) and \
                        isinstance(node.value, ast.Constant) and \
                        isinstance(node.value.value, int) and \
                        not isinstance(node.value.value, bool) and \
                        node.value.value >= _TILE_LITERAL_MIN:
                    self.tile_consts.append(
                        (node.lineno, target.id, node.value.value))
        self.generic_visit(node)

    def _check_blockspec_shape(self, node):
        """Flag tile-sized integer literals in a BlockSpec block shape
        (first positional arg or block_shape kwarg)."""
        self.blockspec_seen = True
        shape = node.args[0] if node.args else next(
            (kw.value for kw in node.keywords
             if kw.arg == "block_shape"), None)
        if not isinstance(shape, (ast.Tuple, ast.List)):
            return
        for elt in shape.elts:
            if isinstance(elt, ast.Constant) and \
                    isinstance(elt.value, int) and \
                    not isinstance(elt.value, bool) and \
                    elt.value >= _TILE_LITERAL_MIN:
                self._emit(
                    "hardcoded-tile-size", "error", elt.lineno,
                    f"integer tile size {elt.value} hardcoded in a "
                    f"pl.BlockSpec block shape: the right tile is a "
                    f"per-device, per-shape search result — take it "
                    f"from apex_tpu.tuning (search space + cache) or "
                    f"ops/pallas_config, the only modules tile numbers "
                    f"may live in")

    # --------------------------------------- rank-unsafe artifact paths

    def _open_write_mode(self, node) -> bool:
        """Is this ``open(...)`` call a write? (positional or ``mode=``
        kwarg; a missing mode is the default read)."""
        mode = node.args[1] if len(node.args) >= 2 else next(
            (kw.value for kw in node.keywords if kw.arg == "mode"),
            None)
        return (isinstance(mode, ast.Constant)
                and isinstance(mode.value, str)
                and mode.value in _WRITE_MODES)

    def _check_rank_unsafe_open(self, node):
        if not node.args:
            return
        if not self._open_write_mode(node):
            return
        path_expr = node.args[0]
        fixed_artifact = None
        has_rank_component = False
        for sub in ast.walk(path_expr):
            if isinstance(sub, ast.Constant) and \
                    isinstance(sub.value, str):
                text = sub.value
                if text.lower().endswith(_ARTIFACT_EXTS):
                    fixed_artifact = text
                if _RANK_COMPONENT_RE.search(text):
                    has_rank_component = True
            elif isinstance(sub, ast.Name):
                if _RANK_COMPONENT_RE.search(sub.id):
                    has_rank_component = True
            elif isinstance(sub, ast.Attribute):
                if _RANK_COMPONENT_RE.search(sub.attr):
                    has_rank_component = True
        if fixed_artifact is None or has_rank_component:
            return
        self._emit(
            "rank-unsafe-artifact-path", "error", node.lineno,
            f"write-mode open() of a fixed artifact path "
            f"({fixed_artifact!r}) in code multiproc workers execute: "
            f"two ranks handed this path clobber or interleave each "
            f"other's telemetry — route it through "
            f"apex_tpu.observability.fleet.rank_path (automatic "
            f".rank{{i}} suffix) or build the name from the "
            f"rank/pid")

    def _check_raw_fp8_cast(self, node):
        """``x.astype(<fp8 dtype>)`` outside the sanctioned owners —
        positional or ``dtype=`` keyword form: a raw cast has neither
        the delayed scale nor the saturation clamp — quantization must
        go through ops.precision."""
        arg = node.args[0] if node.args else next(
            (kw.value for kw in node.keywords if kw.arg == "dtype"),
            None)
        if arg is None:
            return
        name = None
        chain = _attr_chain(arg)
        if chain:
            name = self._resolve(chain)[-1]
        elif isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            name = arg.value
        if name is None or not _FP8_DTYPE_NAME_RE.match(name):
            return
        self._emit(
            "raw-fp8-cast", "error", node.lineno,
            f"raw fp8 cast '.astype({name})': an unscaled, unsaturated "
            f"cast overflows to NaN past the format edge (E4M3 has no "
            f"inf) and flushes small tails to zero — quantize through "
            f"apex_tpu.ops.precision (quantize_fp8 / matmul_fp8) under "
            f"the amp Fp8DelayedScaler's delayed scales; only "
            f"ops/precision.py and amp/ may cast to fp8")

    def _check_memory_introspection(self, node, chain, tail):
        # matched on the attribute, not the chain: the common shapes —
        # `jax.devices()[0].memory_stats()`, `client.live_executables()`
        # — have subscripted/opaque receivers that break the
        # dotted-name chain
        if isinstance(node.func, ast.Attribute) and \
                node.func.attr in _MEMORY_INTROSPECT_ATTRS:
            self._emit(
                "raw-memory-introspection", "error", node.lineno,
                f"direct '.{node.func.attr}()' read: the PJRT "
                f"allocator/executable surface belongs to the memory "
                f"observability tier — use apex_tpu.observability."
                f"memory (device_memory_stats, the compiled-stats "
                f"capture; snapshots, watermarks and gauges ride "
                f"along) or pallas_config.device_hbm_bytes for the "
                f"budget; only those modules may read it directly")
            return
        if tail in _MEMORY_INTROSPECT_JAX_NAMES and chain:
            res = self._resolve(chain)
            if res and res[0] == "jax":
                self._emit(
                    "raw-memory-introspection", "error", node.lineno,
                    f"direct '{'.'.join(chain)}(...)' call: the live-"
                    f"buffer walk sweeps every array on host (and "
                    f"forces backend init through get_backend) — in a "
                    f"step loop it serializes the pipeline like the "
                    f"per-tensor isnan pulls the numerics tier "
                    f"retired. Route through apex_tpu.observability."
                    f"memory (MemoryMonitor's decimated snapshots / "
                    f"memory_snapshot), which also keeps the "
                    f"watermark + top-k accounting OOM forensics "
                    f"depend on")

    def visit_Call(self, node):
        chain = _attr_chain(node.func)
        tail = chain[-1] if chain else None

        if "raw-memory-introspection" in self.checks:
            self._check_memory_introspection(node, chain, tail)

        if "rank-unsafe-artifact-path" in self.checks and \
                isinstance(node.func, ast.Name) and \
                node.func.id == "open":
            self._check_rank_unsafe_open(node)

        if "host-isnan-in-step-loop" in self.checks and \
                self.loop_depth[-1] > 0 and \
                id(node) not in self._isnan_handled:
            if isinstance(node.func, ast.Name) and \
                    node.func.id in ("bool", "float") and node.args and \
                    self._isnan_call_in(node.args[0]) is not None:
                self._emit_isnan_pull(node, node.lineno,
                                      f"via {node.func.id}()")
            elif isinstance(node.func, ast.Attribute) and \
                    node.func.attr in ("item", "tolist") and \
                    self._isnan_call_in(node.func.value) is not None:
                self._emit_isnan_pull(node, node.lineno,
                                      f"via .{node.func.attr}()")

        if tail == "BlockSpec" and "hardcoded-tile-size" in self.checks:
            self._check_blockspec_shape(node)

        if tail == "astype" and "raw-fp8-cast" in self.checks and \
                isinstance(node.func, ast.Attribute):
            self._check_raw_fp8_cast(node)

        if tail == "enter_context":
            # stack.enter_context(span(...)) closes at stack exit —
            # sanction the argument before visiting it
            for arg in node.args:
                if isinstance(arg, ast.Call):
                    self._cm_calls.add(id(arg))
        if tail in _SPAN_NAMES and "unclosed-span" in self.checks and \
                id(node) not in self._cm_calls:
            res = self._resolve(chain)
            if "observability" in res:
                self._emit(
                    "unclosed-span", "error", node.lineno,
                    f"'{'.'.join(chain)}(...)' opened outside a 'with' "
                    f"(or ExitStack.enter_context): a span without its "
                    f"guaranteed close leaks an open-span stack entry "
                    f"the flight recorder reports forever and corrupts "
                    f"later spans' nesting — use 'with "
                    f"{'.'.join(chain)}(...):' around the region")

        if tail == "block_until_ready" or (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "block_until_ready"):
            self.frames[-1]["block"].append(node.lineno)
        # resolve through the import map so `from time import time` and
        # `import time as t` still count as clock reads
        res = self._resolve(chain) if chain else None
        is_clock = (res and len(res) >= 2
                    and (res[-2], res[-1]) in _CLOCK_CALLS) or (
            tail in ("perf_counter", "perf_counter_ns", "monotonic",
                     "default_timer"))
        if is_clock:
            self.frames[-1]["clock"].append(node.lineno)
            self._emit(
                "raw-clock", "error", node.lineno,
                f"direct wall-clock read ('{'.'.join(chain or [tail])}') "
                f"in apex_tpu library code: time through "
                f"apex_tpu.runtime.timing (ends in a blocking sync) or "
                f"an apex_tpu.observability Timer instead — a bare clock "
                f"pair measures dispatch, not device time")

        if self._in_jit():
            if isinstance(node.func, ast.Name) and \
                    node.func.id in _HOST_PULL_NAMES and node.args and \
                    not isinstance(node.args[0], ast.Constant) and \
                    not _is_static_expr(node.args[0]):
                self._emit(
                    "host-in-jit", "error", node.lineno,
                    f"'{node.func.id}(...)' inside a jit-decorated body "
                    f"forces a host pull: it raises on traced values or "
                    f"silently syncs on constants — keep the value on "
                    f"device (jnp) or hoist it out of the jit")
            if res and len(res) >= 2 and \
                    res[0] in ("np", "numpy", "onp") and \
                    res[-1] in _HOST_PULL_NP:
                self._emit(
                    "host-in-jit", "error", node.lineno,
                    f"'{'.'.join(chain)}(...)' inside a jit-decorated "
                    f"body: numpy materializes on host at trace time — "
                    f"use jnp, or hoist the constant out of the jit")
            if isinstance(node.func, ast.Attribute) and \
                    node.func.attr in _HOST_PULL_METHODS:
                self._emit(
                    "host-in-jit", "error", node.lineno,
                    f"'.{node.func.attr}()' inside a jit-decorated body "
                    f"is a device sync / trace error")
            if res and (
                    res[0] == "random"
                    or (len(res) >= 2 and res[0] in ("np", "numpy")
                        and res[1] == "random")):
                self._emit(
                    "rng-in-jit", "error", node.lineno,
                    f"'{'.'.join(chain)}(...)' inside a jit-decorated "
                    f"body: the sample is drawn once at trace time and "
                    f"baked in as a constant — every step reuses it; "
                    f"use jax.random with a threaded key")
        self.generic_visit(node)


def lint_source(source: str, relpath: str, checks=None, abspath=None):
    """Lint one file's source text; returns a list of Findings.

    ``abspath``: the file's absolute path when known (lint_paths passes
    it) — path-scoped checks like raw-clock must not depend on what cwd
    the relpath happened to be computed against."""
    checks = set(checks or AST_CHECKS)
    unknown = checks - set(AST_CHECKS)
    if unknown:
        raise ValueError(f"unknown AST check(s) {sorted(unknown)}; "
                         f"valid: {list(AST_CHECKS)}")
    norm = relpath.replace("\\", "/")
    if any(norm.endswith(allow.replace("\\", "/"))
           for allow in _SYNC_ALLOWLIST):
        checks = checks - {"sync-timing"}
    # raw-clock: library code under an apex_tpu/ package dir only, minus
    # the modules that implement the sanctioned clocks themselves
    if not _raw_clock_applies(abspath or relpath):
        checks = checks - {"raw-clock"}
    # swallowed-exception: step loops live in apex_tpu/ and examples/
    if not _swallowed_exc_applies(abspath or relpath):
        checks = checks - {"swallowed-exception-in-step-loop"}
    # unclosed-span: same ground — instrumented library + example code
    if not _unclosed_span_applies(abspath or relpath):
        checks = checks - {"unclosed-span"}
    # host-isnan: step loops again, minus the numerics package (the
    # sanctioned fused/decimated implementation)
    if not _host_isnan_applies(abspath or relpath):
        checks = checks - {"host-isnan-in-step-loop"}
    # rank-unsafe-artifact-path: the same worker-executed ground, minus
    # the fleet identity package (the sanctioned suffixer)
    if not _rank_unsafe_applies(abspath or relpath):
        checks = checks - {"rank-unsafe-artifact-path"}
    # hardcoded-tile-size: pallas_config + the tuner search space are
    # the sanctioned homes for tile numbers
    if not _tile_size_applies(abspath or relpath):
        checks = checks - {"hardcoded-tile-size"}
    # raw-fp8-cast: ops/precision.py (+ its Pallas kernel) and amp/ are
    # the sanctioned quantization owners
    if not _raw_fp8_applies(abspath or relpath):
        checks = checks - {"raw-fp8-cast"}
    # nondeterministic-collective-order: comms scheduling code only
    # (parallel/, runtime/, distributed/)
    if not _nondet_order_applies(abspath or relpath):
        checks = checks - {"nondeterministic-collective-order"}
    # raw-memory-introspection: the memory observability package and
    # pallas_config are the sanctioned introspection owners
    if not _memory_introspect_applies(abspath or relpath):
        checks = checks - {"raw-memory-introspection"}
    try:
        tree = ast.parse(source, filename=relpath)
    except SyntaxError as e:
        return [Finding("syntax", "error", relpath, e.lineno or 0,
                        "<module>", f"does not parse: {e.msg}")]
    visitor = _Visitor(relpath, relpath, checks)
    visitor.visit(tree)
    # tile-named module constants are only tile sizes when the file
    # actually builds BlockSpecs (a _TILE_ROWS in a data loader is not
    # kernel geometry)
    if "hardcoded-tile-size" in checks and visitor.blockspec_seen:
        for lineno, name, value in visitor.tile_consts:
            visitor.findings.append(Finding(
                "hardcoded-tile-size", "error", relpath, lineno,
                "<module>",
                f"module tile constant {name} = {value} in a file that "
                f"builds pl.BlockSpecs: tile geometry must come from "
                f"apex_tpu.tuning (per-device search + cache) or "
                f"ops/pallas_config — a hardcoded tile outlives the "
                f"hardware it was guessed for"))
    # close the module-level frame (module-scope timing code, e.g. a
    # script body, gets the same sync-timing treatment)
    frame = visitor.frames[0]
    if "sync-timing" in checks and frame["clock"] and frame["block"]:
        for line in frame["block"]:
            visitor.findings.append(Finding(
                "sync-timing", "error", relpath, line, "<module>",
                "block_until_ready in module-level timing code — use "
                "apex_tpu.runtime.timing.sync"))
    lines = source.splitlines()
    return [f for f in visitor.findings
            if not is_suppressed(f, lines)]


def iter_python_files(paths):
    """Expand files/dirs into .py files, skipping caches and build dirs."""
    skip_dirs = {"__pycache__", ".git", "build", ".eggs", "node_modules"}
    for p in paths:
        if os.path.isfile(p):
            if p.endswith(".py"):
                yield p
        elif os.path.isdir(p):
            for root, dirs, files in os.walk(p):
                dirs[:] = sorted(d for d in dirs if d not in skip_dirs
                                 and not d.endswith(".egg-info"))
                for fname in sorted(files):
                    if fname.endswith(".py"):
                        yield os.path.join(root, fname)


def lint_paths(paths, root=None, checks=None):
    """Lint every .py under ``paths``; paths in findings are relative to
    ``root`` (default: cwd)."""
    root = os.path.abspath(root or os.getcwd())
    findings = []
    for fpath in iter_python_files(paths):
        ap = os.path.abspath(fpath)
        rel = os.path.relpath(ap, root) if ap.startswith(root) else fpath
        with open(ap, encoding="utf-8") as f:
            source = f.read()
        findings.extend(lint_source(source, rel, checks, abspath=ap))
    return findings
