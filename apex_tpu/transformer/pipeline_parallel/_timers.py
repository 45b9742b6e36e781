"""Named phase timers (ref apex/transformer/pipeline_parallel/_timers.py).

Since ISSUE 2 this is a thin adapter over the shared telemetry layer:
the actual timing lives in :class:`apex_tpu.observability.Timer`
(device sync via ``runtime.timing`` — the reference's
``torch.cuda.synchronize`` analog — plus a ``timer/<name>`` trace
scope, the nvtx analog the reference pairs with pyprof). What remains here is the
reference-shaped ``Timers.write/log`` API, and the timers register in
the process :class:`~apex_tpu.observability.MetricRegistry` so pipeline
phase times ride the same JSONL export as every other metric.

Usage (identical shape to the reference):

    timers = Timers()
    timers("forward").start()
    out = step(batch)
    timers("forward").stop(out)        # syncs out, records elapsed
    timers.log(["forward"], normalizer=n_iters)
"""

from __future__ import annotations

from typing import Optional

from apex_tpu.observability import MetricRegistry, Timer, get_registry


class _Timer:
    """One named timer (ref _timers.py:6) — adapter over
    ``observability.Timer`` preserving the reference's accumulate /
    elapsed(reset) contract.

    Start/stop/accumulate state is PER INSTANCE (a private Timer, like
    the reference's per-``Timers``-group ``_Timer`` objects — two groups
    must never see each other's running flag), while every recorded
    interval is also observed into the shared registry metric
    ``pp_phase/<name>`` so phase times ride the process JSONL export.
    """

    def __init__(self, name: str, registry: Optional[MetricRegistry] = None):
        self.name_ = name
        reg = registry if registry is not None else get_registry()
        self._timer = Timer(f"pp_phase/{name}", {})   # private state
        self._sink = reg.timer(f"pp_phase/{name}")    # shared metric

    @property
    def started_(self) -> bool:
        return self._timer.running

    @property
    def elapsed_(self) -> float:
        return self._timer.total_elapsed

    def start(self):
        if self._timer.running:
            raise RuntimeError("timer has already been started")
        self._timer.start()

    def stop(self, block_on=None):
        """``block_on``: pytree of device values produced by the timed
        region — synced so the elapsed time covers device execution.
        Omit for host-only regions."""
        if not self._timer.running:
            raise RuntimeError("timer is not started")
        self._sink.observe(self._timer.stop(block_on))

    def reset(self):
        if self._timer.running:
            # a running timer's profiler scope must close or the trace
            # nesting stays unbalanced for the rest of the process
            self._timer.cancel()
        self._timer.reset_total()

    def elapsed(self, reset: bool = True) -> float:
        started = self._timer.running
        if started:
            # split the PRIVATE accumulator only: a poll (write/log on a
            # running timer, reference semantics) is not a completed
            # phase, so the shared pp_phase histogram must not record
            # the fragment — only real stop() calls feed the sink
            self._timer.stop()
        elapsed = self._timer.total_elapsed
        if reset:
            self._timer.reset_total()
        if started:
            self.start()
        return elapsed


class Timers:
    """Group of named timers (ref _timers.py:51 _Timers)."""

    def __init__(self, registry: Optional[MetricRegistry] = None):
        self.timers = {}
        self._registry = registry

    def __call__(self, name: str) -> _Timer:
        if name not in self.timers:
            self.timers[name] = _Timer(name, self._registry)
        return self.timers[name]

    def write(self, names, writer, iteration, normalizer: float = 1.0,
              reset: bool = False):
        """Write timings to a tensorboard-style ``writer`` (anything with
        ``add_scalar(tag, value, step)``)."""
        assert normalizer > 0.0
        for name in names:
            if name not in self.timers:
                continue  # same contract as log(): unstarted phases skip
            value = self.timers[name].elapsed(reset=reset) / normalizer
            writer.add_scalar(f"{name}-time", value, iteration)

    def log(self, names, normalizer: float = 1.0, reset: bool = True,
            printer: Optional[callable] = None):
        assert normalizer > 0.0
        string = "time (ms)"
        for name in names:
            if name not in self.timers:
                continue  # never-started phases just don't report
            elapsed_time = (self.timers[name].elapsed(reset=reset)
                            * 1000.0 / normalizer)
            string += f" | {name}: {elapsed_time:.2f}"
        if printer is not None:
            printer(string)
        else:
            # flushed: timing lines must survive a watchdog os._exit
            print(string, flush=True)
