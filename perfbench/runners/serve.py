"""Serving cells: `ServingEngine.submit` / `step` under one general load loop.

One thread offers the schedule of `perfbench/traffic.py` on the host's clock
and steps the engine between arrivals. An open loop has a lead-in (the same
traffic, before the window, so that the window opens on a steady engine), the
window, and a lead-out that keeps the load on while the window's last requests
finish; only requests due in the window are measured, each from its due time.
A backlog queues everything before the lead-in and counts the tokens the window
generates. Weights come from the seed, on the device, in bfloat16.
"""

import gc
import math
import time

import numpy as np

from perfbench import stats, traffic
from perfbench.references.common import seed_words

PAD_TO = 512                     # reference sequences are padded to this


def model_config(cfg):
    import jax.numpy as jnp
    from apex_tpu.models import llama

    return llama.LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=cfg["rope_theta"], rms_eps=cfg["rms_norm_eps"],
        dtype=jnp.dtype(cfg["torch_dtype"]),
        tie_embeddings=cfg["tie_word_embeddings"])


class Server:
    """The engine with its weights: the object the window drives."""

    def __init__(self, run):
        import jax

        self.run, self.cfg, self.ref = run, run.config, run.reference()
        self.init = jax.jit(lambda lo, hi: self.ref.init(lo, hi, self.cfg))
        self.params = self.engine = None
        self.requests = []           # (planned, Request) in submit order

    def start(self, seed):
        from apex_tpu.serving import ServingEngine

        self.params = self.init(*seed_words(seed))
        self.engine = ServingEngine(self.params, model_config(self.cfg),
                                    **self.run.mix["engine"])
        self.requests = []

    def submit(self, planned, t0):
        """The window's own entry: `engine.submit`."""
        self.engine.submit(planned.prompt, planned.max_new_tokens,
                           rid=planned.index, arrival_s=t0 + planned.due_s)
        self.requests.append((planned, self.engine.scheduler.queue[-1]))

    def will_admit(self):
        sched = self.engine.scheduler
        return bool(sched.queue) and None in sched.slots and \
            sched.cache.alloc.can_alloc(sched.pages_needed(sched.queue[0]))

    def warm(self, plan):
        """Compile what this schedule can reach and nothing else: one prompt
        of each padded length through prefill, the page write and decode."""
        page = self.run.mix["engine"]["page_size"]
        for length in traffic.prompt_buckets(plan, page):
            self.engine.submit(np.zeros(length, np.int32), 2)
        while self.engine.pending:
            self.engine.step()
        self.engine.completed.clear()
        self.engine.results.clear()

    def tokens_out(self):
        return sum(len(req.tokens) for _, req in self.requests)

    def free(self):
        self.engine = None
        gc.collect()


def serve(run, server, plan):
    """Offer `plan` and step the engine until the window's requests are done.
    Returns (t0, t_open, t_close): the window's scheduled start, and the step
    boundaries at which it opened and closed, on the host's clock."""
    mix, engine, sched = run.mix, server.engine, server.engine.scheduler
    backlog = mix["arrivals"]["process"] == "backlog"
    seconds = run.seconds
    t0 = time.monotonic() + float(mix.get("lead_in_s", 0.0))
    trace_from = seconds - run.trace_seconds() if run.trace_on else None
    drain_limit = float(mix.get("drain_limit_s", 60.0))
    t_open = t_close = None
    tokens_open = tokens_close = 0
    nxt = 0
    while True:
        now = time.monotonic()
        rel = now - t0
        if t_open is None and rel >= 0:
            t_open, tokens_open = now, server.tokens_out()
            run.open_window(now)
        if t_close is None and rel >= seconds:
            t_close, tokens_close = now, server.tokens_out()
            run.close_window(now)
            run.stop_trace()
        if trace_from is not None and rel >= trace_from:
            run.start_trace()
            trace_from = None
        if nxt < len(plan) and plan[nxt].due_s <= rel:
            with run.span("bench:submit"):
                while nxt < len(plan) and plan[nxt].due_s <= rel:
                    server.submit(plan[nxt], t0)
                    nxt += 1
        if t_close is not None:
            if backlog:
                break
            if rel >= seconds + drain_limit or all(
                    req.state == "done" for p, req in server.requests
                    if p.in_window):
                break
        if engine.pending:
            admits = server.will_admit()
            before = sched.prefill_count
            with run.span("bench:step_prefill" if admits
                          else "bench:step_decode"):
                engine.step()
            if t_open is not None and t_close is None:
                live = [r for r in sched.slots if r is not None]
                run.series["steps"].append({
                    "t": time.monotonic(), "admitted": sched.prefill_count
                    - before, "rows": len(live),
                    "context": sum(len(r.prompt) + len(r.tokens)
                                   for r in live),
                    "occupancy": sched.occupancy(),
                    "utilization": sched.cache.utilization()})
        elif nxt < len(plan):
            time.sleep(max(0.0, min(0.002, plan[nxt].due_s - rel)))
        else:
            break
    if t_close is None:
        t_close, tokens_close = time.monotonic(), server.tokens_out()
        run.close_window(t_close)
        run.stop_trace()
    run.counters.update(tokens_in_window=tokens_close - tokens_open,
                        t_end=time.monotonic())
    return t0, t_open, t_close


def measure(run, server, t0, t_close):
    """End-to-end metrics and the series the per-layer readers use."""
    backlog = run.mix["arrivals"]["process"] == "backlog"
    measured = [(p, r) for p, r in server.requests if p.in_window]
    if backlog:
        run.e2e["serve_tokens_per_s"] = stats.window_rate(
            run.counters["tokens_in_window"], run.window_s)
        started = [r for _, r in measured if r.first_token_s is not None
                   and r.first_token_s <= t_close]
        run.counters["requests_in_window"] = len(started)
        return len(started), 0
    t_end = run.counters["t_end"]
    ttft, tpot, late = [], [], []
    for p, r in measured:
        # a request that never got its first token, or never finished, counts
        # with the wait it had had when the run gave up on it: the worst
        due = t0 + p.due_s
        late.append((r.submit_s - due) * 1e3)
        first = r.first_token_s if r.first_token_s is not None else t_end
        ttft.append((first - due) * 1e3)
        if len(r.tokens) > 1 or r.state != "done":
            last = r.finish_s if r.state == "done" else t_end
            tpot.append((last - first) * 1e3 / max(1, len(r.tokens) - 1))
    run.e2e["ttft_p90_ms"] = stats.percentile(ttft, 90)
    run.e2e["ttft_mean_ms"] = sum(ttft) / len(ttft)
    run.e2e["tpot_p90_ms"] = stats.percentile(tpot, 90)
    run.series["lateness_ms"] = late
    run.series["ttft_ms"] = ttft
    run.series["tpot_ms"] = tpot
    run.counters["requests_in_window"] = len(measured)
    failed = sum(r.state != "done" for _, r in measured)
    return len(measured), failed


def drive(run, server):
    """The served part of a run: weights and engine from the run's seed,
    warm-up, lead-in, window and drain. Returns (attempted, failed, t_close)."""
    from apex_tpu.observability import recompile

    listener = recompile.install()
    server.start(run.seed)
    run.mark("engine_made")
    plan = traffic.serve_schedule(run.mix, run.seed, run.seconds,
                                  run.config["vocab_size"])
    server.warm(plan)
    run.mark("warmed")
    compiles0 = listener.backend_compiles()
    t0, _, t_close = serve(run, server, plan)
    run.counters["compiles_in_window"] = (listener.backend_compiles()
                                          - compiles0)
    attempted, failed = measure(run, server, t0, t_close)
    return attempted, failed, t_close


def sample_served(run, server, t_close):
    """Finished requests to check: the longest, then others drawn from the
    seed until some hundreds of served tokens are in hand."""
    backlog = run.mix["arrivals"]["process"] == "backlog"
    done = [(p, r) for p, r in server.requests
            if p.in_window and r.state == "done"
            and (not backlog or r.finish_s <= t_close)]
    if not done:
        return []
    done.sort(key=lambda pr: -(len(pr[0].prompt) + len(pr[1].tokens)))
    rng = traffic.rng_for(run.seed, 11)
    order = [0] + [1 + int(i) for i in rng.permutation(len(done) - 1)]
    want, picked, have = run.mix["check"]["sample_tokens"], [], 0
    for i in order:
        p, r = done[i]
        picked.append((np.asarray(p.prompt, np.int32),
                       np.asarray(r.tokens, np.int32)))
        have += len(r.tokens)
        if have >= want:
            break
    return picked


def check_served(run, server, picked, precision=None):
    """Run the reference once over each prompt with its served tokens. Returns
    the widest gap by which a served token's logit lies below the reference's
    best; with `precision`, also the widest such gap of the token that the
    reference at that lower precision puts first (the control)."""
    import jax
    import jax.numpy as jnp

    ref, cfg = server.ref, server.cfg
    cap = run.mix["engine"]["max_new_cap"]

    @jax.jit
    def gaps(params, tokens, rows, served):
        logits = ref.row_logits(params, tokens, rows, cfg)
        return ref.served_gaps(logits, served)

    @jax.jit
    def control_gaps(params, tokens, rows):
        logits = ref.row_logits(params, tokens, rows, cfg)
        low = ref.row_logits(params, tokens, rows, cfg, precision)
        return ref.served_gaps(logits, jnp.argmax(low, axis=-1))

    widest = control = 0.0
    for prompt, served in picked:
        n, p = len(served), len(prompt)
        seq = np.concatenate([prompt, served[:-1]])
        padded = np.zeros(math.ceil(len(seq) / PAD_TO) * PAD_TO, np.int32)
        padded[:len(seq)] = seq
        rows = np.minimum(p - 1 + np.arange(cap), len(seq) - 1)
        tokens = np.zeros(cap, np.int32)
        tokens[:n] = served
        g = np.asarray(gaps(server.params, padded, rows, tokens))[:n]
        widest = max(widest, float(g.max()))
        if precision:
            c = np.asarray(control_gaps(server.params, padded, rows))[:n]
            control = max(control, float(c.max()))
    return (widest, control) if precision else widest


def run(run):
    run.open_device()
    server = Server(run)
    attempted, failed, t_close = drive(run, server)
    run.read_memory_peak()
    picked = sample_served(run, server, t_close)
    server.free()
    limit = run.mix["check"]["logit_gap_limit"]
    compared = {"requests_to_check_missing": (float(not picked), 0.0)}
    if picked:
        compared["served_logit_gap"] = (check_served(run, server, picked),
                                        limit)
    return run.result(attempted, failed, compared)


def calibrate(workload, seeds, control_seeds, seconds, emit, **run_args):
    """Readings that the limit is set from, in one process: for every seed a
    short window at the cell's own load and the widest gap of its sample; for
    the control seeds also the widest gap of the token that the reference at
    fp8 puts first, at the same positions."""
    from perfbench import harness

    for seed in seeds:
        t0 = time.monotonic()
        run = harness.Run(workload, seed, seconds, 0, **run_args)
        run.open_device()
        server = Server(run)
        attempted, failed, t_close = drive(run, server)
        picked = sample_served(run, server, t_close)
        server.free()
        record = {"workload": workload, "seed": seed, "attempted": attempted,
                  "failed": failed, "sampled_requests": len(picked),
                  "sampled_tokens": sum(len(s) for _, s in picked),
                  "metrics": dict(run.e2e)}
        if seed in control_seeds:
            record["served_logit_gap"], record["control_fp8_gap"] = \
                check_served(run, server, picked, precision="fp8")
        else:
            record["served_logit_gap"] = check_served(run, server, picked)
        server.params = None
        gc.collect()
        record["seconds"] = time.monotonic() - t0
        emit(record)
