"""What every run shares: the cell's files, the device, spans, the trace, the
per-layer readers and the result line. Driven by data: a cell names its
configuration and its traffic mix, `perfbench/configs/<config>.json` names the
runner and the reference, `perfbench/traffic/<traffic>.json` holds the mix,
and each per-layer metric of BENCHMARK.json has `perfbench/metrics/<name>.json`
naming its reader in `perfbench/readers/`. Adding any of them adds files only.
"""

import collections
import contextlib
import importlib
import json
import os
import shutil
import sys
import time

from perfbench import peaks, trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find(entries, name, what):
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise SystemExit(f"perfbench: no {what} named {name!r} in BENCHMARK.json")


class Run:
    """One run of one cell: what it was asked, and what it has read so far."""

    def __init__(self, workload, seed, seconds, trace, t_start=None, root=ROOT,
                 require_tpu=True):
        self.root, self.seed, self.seconds = root, int(seed), float(seconds)
        self.trace_on, self.require_tpu = bool(trace), require_tpu
        self.t_start = time.monotonic() if t_start is None else t_start
        self.bench = load_json(root, "BENCHMARK.json")
        self.cell = find(self.bench["workloads"], workload, "workload")
        self.config_entry = find(self.bench["configs"], self.cell["config"],
                                 "configuration")
        self.config = load_json(root, self.config_entry["file"])
        self.mix = load_json(root, "perfbench", "traffic",
                             self.cell["traffic"] + ".json")
        self.chips = int(self.cell["chips"])
        self.counters = {}                      # name -> number
        self.series = collections.defaultdict(list)   # name -> numbers
        self.e2e = {}                           # end-to-end metric -> value
        self.trace = None                       # trace_reduce.Trace
        self.window_s = None                    # measured window, host clock
        self.device = None
        self.peak = None
        self._tracing = False
        self.marks = []                         # (what, seconds since start)

    def mark(self, what):
        """A point of set-up, for the split that PERF.md reports."""
        self.marks.append((what, time.monotonic() - self.t_start))

    # ------------------------------------------------------------- modules
    def runner(self):
        return importlib.import_module(
            "perfbench.runners." + self.config["runner"])

    def reference(self):
        return importlib.import_module(
            "perfbench.references." + self.config["reference"])

    # -------------------------------------------------------------- device
    def open_device(self):
        """Find the chips, refuse anything else, place the compile cache."""
        import jax

        self.mark("jax_imported")
        backend = jax.default_backend()
        self.mark("backend_up")
        if self.require_tpu and backend != "tpu":
            raise SystemExit(f"perfbench: no TPU: jax selected the "
                             f"{backend!r} backend; nothing was run")
        devices = jax.devices()
        if len(devices) < self.chips:
            raise SystemExit(f"perfbench: the cell needs {self.chips} "
                             f"chip(s), jax sees {len(devices)}")
        from apex_tpu.runtime.compile_cache import enable_compile_cache
        enable_compile_cache()
        dev = devices[0]
        self.device = {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(devices)}
        if backend == "tpu":
            self.peak = peaks.peak(dev.device_kind)
        self.mark("device_open")
        return devices[:self.chips]

    def read_memory_peak(self):
        import jax

        stats = [d.memory_stats() or {} for d in jax.devices()[:self.chips]]
        self.device["memory_peak_bytes"] = max(
            int(s.get("peak_bytes_in_use", 0)) for s in stats)
        self.counters["peak_hbm_bytes"] = self.device["memory_peak_bytes"]
        if stats[0]:
            print(f"memory_stats of the first chip: {stats[0]}",
                  file=sys.stderr)

    def open_window(self, now=None):
        """The window opens now: everything before it was set-up."""
        now = time.monotonic() if now is None else now
        self.counters["t_open"] = now
        self.e2e["setup_s"] = now - self.t_start
        self.marks.append(("window_open", self.e2e["setup_s"]))

    def close_window(self, now=None):
        self.counters["t_close"] = time.monotonic() if now is None else now
        self.window_s = self.counters["t_close"] - self.counters["t_open"]

    def in_window(self, t):
        return self.counters["t_open"] <= t <= self.counters["t_close"]

    # --------------------------------------------------------------- spans
    @contextlib.contextmanager
    def span(self, name):
        """A `bench:` span: in the profiler's trace when one is being taken,
        and (end time, milliseconds) by the host's clock in
        `series['span/<name>']`."""
        import jax

        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation(name):
            yield
        t1 = time.monotonic()
        self.series["span/" + name].append((t1, (t1 - t0) * 1e3))

    # --------------------------------------------------------------- trace
    @property
    def trace_dir(self):
        return os.path.join(self.root, ".perfbench_trace", self.cell["name"])

    def trace_seconds(self):
        return min(float(self.mix.get("trace_s", 6.0)), self.seconds)

    def start_trace(self):
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self._window_span = jax.profiler.TraceAnnotation(
            trace_reduce.WINDOW_SPAN)
        self._window_span.__enter__()
        self._tracing = True
        self.counters["trace_t0"] = time.monotonic()

    def stop_trace(self):
        import jax

        if not self._tracing:
            return
        self._window_span.__exit__(None, None, None)
        self.counters["trace_t1"] = time.monotonic()
        jax.profiler.stop_trace()
        self._tracing = False
        self.trace = trace_reduce.load(trace_reduce.find_xplane(self.trace_dir))
        shutil.rmtree(self.trace_dir, ignore_errors=True)

    def in_trace(self, t):
        """Whether host-clock time `t` fell inside the traced window."""
        return (self.counters.get("trace_t0", float("inf")) <= t
                <= self.counters.get("trace_t1", time.monotonic()))

    # ------------------------------------------------------------- results
    def _reports(self, metric):
        return self.cell["name"] in metric.get(
            "workloads", [self.cell["name"]])

    def end_to_end_metrics(self):
        out = {}
        for metric in self.bench["end_to_end"]:
            if self._reports(metric) and metric["name"] in self.e2e:
                out[metric["name"]] = {"value": self.e2e[metric["name"]],
                                       "unit": metric["unit"]}
        return out

    def per_layer_metrics(self):
        out = {}
        for metric in self.bench["per_layer"]:
            if not self._reports(metric):
                continue
            spec = load_json(self.root, "perfbench", "metrics",
                             metric["name"] + ".json")
            reader = importlib.import_module(
                "perfbench.readers." + spec["reader"])
            value = reader.read(self, **spec.get("args", {}))
            if value is not None:
                out[metric["name"]] = {"value": value, "unit": metric["unit"]}
        return out

    def result(self, attempted, failed, compared):
        """The last line of standard output. `compared` maps a short name to
        (number, limit); the run is correct when every number is finite and at
        or under its limit and nothing failed to come back (`failed` is 0)."""
        ok = bool(compared) and all(
            v is not None and v == v and v <= limit
            for v, limit in compared.values()) and not failed
        line = {"correct": ok, "attempted": int(attempted),
                "failed": int(failed)}
        if self.trace_on:
            line["metrics"] = self.per_layer_metrics()
            if self.trace is not None:
                self.device["busy_s"] = trace_reduce.busy_s(self.trace)
                self.device["window_s"] = self.trace.window_s
                line["breakdown"] = trace_reduce.breakdown(self.trace)
        else:
            line["metrics"] = self.end_to_end_metrics()
        line["device"] = self.device
        line["setup_split_s"] = {what: at for what, at in self.marks}
        # a number that is not finite would not be JSON: it reads as null
        line["compared"] = {
            k: {"value": v if v is not None and abs(v) < float("inf")
                else None, "limit": limit}
            for k, (v, limit) in compared.items()}
        return line


def emit(line):
    """Each number compared beside its limit as the last lines of standard
    error, then the result as the last line of standard output."""
    print("set-up reached, seconds from process start: " + ", ".join(
        f"{k} {v:.1f}" for k, v in line.get("setup_split_s", {}).items()),
        file=sys.stderr)
    for name, pair in line["compared"].items():
        print(f"compared {name}: {pair['value']} (limit {pair['limit']})",
              file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
