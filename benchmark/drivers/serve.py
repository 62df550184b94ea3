"""Driver of the serving cells: ``serving.engine.ServingEngine`` under a
traffic mix, one process, one thread.

Set-up: weights on the device from the seed (``lib/reference.make_params``:
the benchmark's, handed to the program and later to the plain reference),
the engine as a user builds it, one request per prompt length of the mix
and one per context bucket run through the engine so that every program
the window can use is compiled,
then a ramp of the cell's own traffic so that the window opens on a full,
mixed batch: the first ``ramp_population`` requests are cut to a seeded
share of their answers (as if they had been running for a while, so that
they end at different times, as in a steady state), and the ramp lasts
``ramp_steps`` engine steps (a backlog) or ``ramp_s`` seconds (a
schedule).  All of that is ``setup_s``.

The window: the harness submits each request when it is due on the
generator's schedule (a backlog keeps ``queue_floor`` requests queued),
calls ``engine.step()`` back to back and, after each step, reads on its
own clock which requests got tokens.  A token counts when the step that
sampled it has returned: the engine hands tokens over nowhere else.

The check, after the window: a seeded sample of the requests finished in
it, the longest among them, goes once through the plain reference, and
each served token's logit is held against the reference's best.
"""

from __future__ import annotations

import gc
import math
import time

import numpy as np

COHORT_RID = 1_000_000_000      # request ids of the ramp's first cohort



class _Recorder:
    """What the program's ``recorder=`` seam is given in a traced run."""

    def __init__(self):
        self.records = []

    def record(self, **rec):
        self.records.append(rec)


class _Watch:
    """The harness's own view of the requests: when each was due, when it
    was submitted, when its first and its latest token were seen."""

    def __init__(self):
        self.due = {}
        self.submitted = {}
        self.first = {}
        self.last = {}
        self.tokens = {}
        self.done = {}
        self.prompt_len = {}
        self.live = set()

    def observe(self, engine, now) -> int:
        """Read the engine after a step; returns tokens newly seen."""
        new = 0
        for s in engine.slots:
            if s is None:
                continue
            rid = s.orig.rid
            n = engine._delivered(s)
            had = self.tokens.get(rid, 0)
            if n > had:
                new += n - had
                self.tokens[rid] = n
                self.first.setdefault(rid, now)
                self.last[rid] = now
        for rid in [r for r in self.live if r in engine.outputs]:
            n = len(engine.outputs[rid]) - self.prompt_len[rid]
            new += n - self.tokens.get(rid, 0)
            self.tokens[rid] = n
            self.first.setdefault(rid, now)
            self.last[rid] = now
            self.done[rid] = now
            self.live.discard(rid)
        return new


def build(run):
    import jax

    from flashmoe_tpu.serving.engine import Request, ServeConfig, ServingEngine

    ref, traffic = run.lib("reference"), run.lib("traffic")
    spec, config = run.cell.spec, run.cell.config
    dims = ref.model_dims(config)
    cfg = run.program_config()
    params = jax.block_until_ready(ref.make_params(run.seed, dims))
    serve = ServeConfig(**spec["engine"])
    recorder = _Recorder() if run.trace else None
    engine = ServingEngine(params, cfg, serve, recorder=recorder)
    run.say(phase="weights", s=round(run.clock() - run.t_start, 3))

    # every shape the window can use.  (a) each prompt LENGTH of the mix
    # once, all submitted together, two tokens each: the engine pads a
    # prompt to its bucket with a program of its own per length, besides
    # the prefill program per bucket, and fills every slot index on the
    # way.  (b) each context bucket of the decode step, by one request
    # alone whose context starts in it.
    mix = spec["traffic"]
    means = traffic.mix_means(mix)
    rng = np.random.default_rng(run.seed & 0xFFFFFFFF)
    toks = lambda n: tuple(int(t) for t in rng.integers(1, dims["vocab"], n))
    lengths = sorted(set(traffic.quantile_sizes(
        mix["prompt_len"], int(mix.get("block", 64)))))
    engine.run([Request(rid=-1 - j, prompt=toks(n), max_new_tokens=2)
                for j, n in enumerate(lengths)])
    span = serve.ctx_bucket_pages * serve.page_size
    longest = means["prompt_max"] + means["output_max"]
    for j, lo in enumerate(range(0, longest, span)):
        n = min(max(lo, lengths[0]), means["prompt_max"])
        engine.run([Request(rid=-1000 - j, prompt=toks(n),
                            max_new_tokens=max(2, lo + 2 - n))])
    run.say(phase="warm", s=round(run.clock() - run.t_start, 3),
            prefill_buckets=sorted(engine.stats["prefill_buckets"]),
            decode_buckets=sorted(engine.stats["decode_buckets"]))
    warm_shapes = (set(engine.stats["prefill_buckets"]),
                   set(engine.stats["decode_buckets"]))

    engine.outputs.clear()
    arrivals = traffic.stream(mix, run.seed, dims["vocab"])
    return {"engine": engine, "params": params, "dims": dims,
            "serve": serve, "arrivals": arrivals, "watch": _Watch(),
            "recorder": recorder, "Request": Request,
            "warm_shapes": warm_shapes, "means": means}


def measure(state, run):
    import jax

    engine, watch = state["engine"], state["watch"]
    mix = run.cell.spec["traffic"]
    clock = run.clock
    Request = state["Request"]
    backlog = mix["arrivals"]["kind"] == "backlog"
    floor = int(mix.get("queue_floor", 64))
    ramp_s = float(mix.get("ramp_s", 0.0))
    ramp_steps = int(mix.get("ramp_steps", 0))
    aged = int(mix.get("ramp_population", 0))
    ages = (np.random.default_rng([run.seed & 0xFFFFFFFF, 11])
            .permutation(aged) + 0.5) / max(aged, 1)
    drain_s = 0.0 if backlog else float(mix.get("drain_s", 30.0))
    arrivals = state["arrivals"]
    nxt = next(arrivals)             # the next request not yet submitted
    annotate = jax.profiler.TraceAnnotation

    t0 = clock()                     # the generator starts offering
    # the first cohort is there when the offering starts: requests of the
    # same mix from a stream of their own, cut to a seeded share of their
    # answers; never judged, though the tokens they get in the window count
    cohort = run.lib("traffic").stream(mix, run.seed + 0x5EED,
                                    state["dims"]["vocab"], COHORT_RID)
    for j in range(aged):
        a = next(cohort)
        watch.prompt_len[a.rid] = len(a.prompt)
        watch.live.add(a.rid)
        engine.submit(Request(
            rid=a.rid, prompt=a.prompt, max_new_tokens=max(
                1, math.ceil(a.max_new_tokens * ages[j]))))
    if backlog:
        w0 = w1 = float("inf")       # set when the ramp's steps are done
    else:
        w0, w1 = t0 + ramp_s, t0 + ramp_s + run.seconds
    steps_taken = 0
    step_ms, step_ctx, step_active = [], [], []
    window_tokens = 0
    lateness = []
    while True:
        now = clock()
        if now >= w1 + drain_s:
            break
        offering = now < w1
        if offering:
            with annotate("bench.submit"):
                while (len(engine.queue) < floor if backlog
                       else nxt.due_s <= now - t0):
                    a, nxt = nxt, next(arrivals)
                    due = t0 if backlog else t0 + a.due_s
                    watch.due[a.rid] = due
                    watch.submitted[a.rid] = now
                    watch.prompt_len[a.rid] = len(a.prompt)
                    watch.live.add(a.rid)
                    if not backlog:
                        lateness.append(now - due)
                    engine.submit(Request(
                        rid=a.rid, prompt=a.prompt,
                        max_new_tokens=a.max_new_tokens))
        elif not engine.pending():
            break                    # drained before the bound
        if not engine.pending():
            time.sleep(max(0.0, min(t0 + nxt.due_s, w1) - clock()))
            continue
        if run.tracer is not None and now >= w0:
            run.tracer.tick(now - w0, clock)
        in_window = w0 <= now < w1
        if in_window and run.trace:
            step_ctx.append(sum(s.length for s in engine.slots
                                if s is not None))
            step_active.append(sum(s is not None for s in engine.slots))
        ts = clock()
        with annotate("bench.engine_step"):
            engine.step()
        te = clock()
        with annotate("bench.observe"):
            new = watch.observe(engine, te)
        steps_taken += 1
        if backlog and steps_taken == ramp_steps:
            w0, w1 = te, te + run.seconds
            continue
        if w0 <= te < w1:
            window_tokens += new
            step_ms.append((te - ts) * 1e3)
    t_end = clock()
    if run.tracer is not None:
        run.tracer.finish()

    shapes_now = (set(engine.stats["prefill_buckets"]),
                  set(engine.stats["decode_buckets"]))
    new_shapes = [sorted(a - b) for a, b in
                  zip(shapes_now, state["warm_shapes"])]

    traffic = run.lib("traffic")
    if backlog:
        # a backlog is never drained: the requests judged are those the
        # window finished; what it left in flight or queued has not failed
        judged = [r for r, t in watch.done.items()
                  if w0 <= t < w1 and r < COHORT_RID]
    else:
        # open loop: every request DUE in the window is judged, and one
        # that the drain did not finish has failed
        judged = [r for r, due in watch.due.items() if w0 <= due < w1]
    finished = [r for r in judged if r in watch.done]
    attempted, failed = len(judged), len(judged) - len(finished)
    e2e = {"serve_tokens_per_s": window_tokens / run.seconds}
    notes = {"window_tokens": window_tokens, "steps": len(step_ms),
             "step_ms_p50_mean_max": [
                 round(float(np.median(step_ms)), 3),
                 round(float(np.mean(step_ms)), 3),
                 round(float(np.max(step_ms)), 3)] if step_ms else None,
             "decode_buckets_seen": sorted(engine.stats["decode_buckets"]),
             "requests_due_in_window": len(judged),
             "finished": len(finished),
             "finished_in_window": sum(
                 1 for rid, t in watch.done.items() if w0 <= t < w1),
             "shapes_first_met_after_warmup": new_shapes,
             "generator_lateness_ms_max": 1e3 * max(lateness, default=0.0),
             "evictions": engine.stats["evictions"],
             "drain_s": max(0.0, t_end - w1)}
    if finished:
        ttft = [1e3 * (watch.first[r] - watch.due[r]) for r in finished]
        tpot = [1e3 * (watch.last[r] - watch.first[r])
                / (watch.tokens[r] - 1)
                for r in finished if watch.tokens[r] > 1]
        late = [1e3 * (watch.submitted[r] - watch.due[r]) for r in judged]
        e2e["ttft_p95_ms"] = traffic.pctl(ttft, 0.95)
        e2e["tpot_p95_ms"] = traffic.pctl(tpot, 0.95)
        notes.update(ttft_p50_ms=traffic.pctl(ttft, 0.5),
                     tpot_p50_ms=traffic.pctl(tpot, 0.5),
                     samples_beyond_p95=len(ttft)
                     - int(np.ceil(0.95 * len(ttft))),
                     generator_lateness_ms_p95=traffic.pctl(late, 0.95))
    harness = {"engine_step_ms": step_ms}
    if step_ctx:
        harness["ctx_tokens_mean"] = float(np.mean(step_ctx))
        harness["active_slots_mean"] = float(np.mean(step_active))
    state["window"] = (w0, w1)
    return {"end_to_end": e2e, "window_start": w0, "attempted": attempted,
            "failed": failed, "notes": notes, "harness": harness,
            "records": state["recorder"].records if state["recorder"]
            else []}


def sample_streams(state, run, n):
    """A seeded sample of the requests finished in the window, with the
    one of most served tokens in it: [(prompt, served_tokens)]."""
    engine, watch = state["engine"], state["watch"]
    w0, _ = state["window"]
    done = sorted(rid for rid, t in watch.done.items()
                  if t >= w0 and rid < COHORT_RID)
    if not done:
        return []
    rng = np.random.default_rng([run.seed & 0xFFFFFFFF, 7])
    longest = max(done, key=lambda r: (watch.tokens[r], -r))
    rest = [r for r in done if r != longest]
    picks = [longest] + [int(r) for r in rng.choice(
        rest, size=min(n - 1, len(rest)), replace=False)]
    out = []
    for rid in picks:
        full = engine.outputs[rid]
        t0 = watch.prompt_len[rid]
        out.append((tuple(full[:t0]), tuple(full[t0:])))
    return out


def check(state, run):
    ref = run.lib("reference")
    spec = run.cell.spec["check"]
    streams = sample_streams(state, run, int(spec["streams"]))
    engine, serve = state["engine"], state["serve"]
    # the program's state goes before the reference's copies come
    engine.close()
    engine.cache = None
    engine._logits = None
    gc.collect()
    t0 = run.clock()
    if not streams:
        return {"correct": False, "compared": [
            {"name": "served_streams", "value": 0, "limit": 1, "ok": False}],
            "notes": {}}
    t_pad = serve.max_context
    r_pad = state["means"]["output_max"]
    got = ref.served_token_gaps(state["params"], state["dims"], streams,
                                t_pad, r_pad)
    limits = spec["limits"]
    compared = []
    for name, value in (("served_gap_widest", got["widest"]),
                        ("served_gap_mean", got["mean"])):
        limit = limits.get(name)
        compared.append({"name": name, "value": value, "limit": limit,
                         "ok": limit is None or value <= limit})
    notes = {"streams": got["per_stream"], "tokens": got["tokens"],
             "reference_s": round(run.clock() - t0, 3)}
    if run.control:
        low = ref.served_token_gaps(state["params"], state["dims"], streams,
                                    t_pad, r_pad, control=spec["control"])
        notes["control"] = {"precision": spec["control"],
                            "served_gap_widest": low["widest"],
                            "served_gap_mean": low["mean"],
                            "streams": low["per_stream"]}
    return {"correct": all(c["ok"] for c in compared) and got["tokens"] > 0,
            "compared": compared, "notes": notes}


def close(state):
    state.clear()
    gc.collect()
