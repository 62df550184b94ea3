"""Driver of the serving cells whose layers keep a recurrent state by
SLOT (delta-rule linear attention): ``drivers/serve_mla.py``'s run (the
cell's own ``reference`` file, everything else ``drivers/serve.py``'s)
with one more comparison in the check, because the served tokens cannot
tell a float32 state from a rounded one (PERF.md section 4).

After the window the engine's slots still hold the requests in flight,
each with the state the TIMED programs left it: whole-prompt or chunked
prefill, then one decode step a token.  The ``check.state_streams`` slots
that have decoded longest are read back (the window is over: this costs
it nothing), the reference runs its recurrence over the tokens each has
consumed, and the first 'kda' layer's state is held to it
(``state_gap``; ``lib/reference_ling3.state_gaps`` says why that layer).
``--control 1`` also reads every state layer of the first slot and the
control's gap: the reference's own state rounded to
``check.state_control`` after every token.

Besides, the slowest step of the window is kept with its phases (the
``serve_step`` record ``engine.step()`` returns, whether or not anyone
reads it), for the single long steps of PERF.md section 7.
"""

from __future__ import annotations

import importlib.util
import os
import sys

import numpy as np


def _base():
    name = "benchdriver_serve_mla"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "serve_mla.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


def build(run):
    return _base().build(run)


def measure(state, run):
    engine = state["engine"]
    steps, step = [], engine.step

    def watched():
        t = run.clock()
        rec = step()
        steps.append((t, rec))
        return rec

    engine.step = watched
    try:
        measured = _base().measure(state, run)
    finally:
        del engine.step
    in_window = [rec for t, rec in steps if t >= measured["window_start"]]
    slowest = max(in_window, key=lambda rec: rec["step_ms"], default={})
    measured["notes"]["slowest_step"] = {k: slowest[k] for k in (
        "step", "step_ms", "phase_ms", "compiles", "compile_ms", "active",
        "tokens", "ctx_pages") if k in slowest}
    return measured


def _slot_states(engine, n, all_layers_of_first):
    """[(tokens consumed, [state per layer])] of the ``n`` decoding slots
    with most tokens decoded; the first 'kda' layer's state, and every
    such layer's for the first slot where asked."""
    decoding = [(len(s.emitted), i) for i, s in enumerate(engine.slots)
                if s is not None and s.prefill_pos is None and s.emitted]
    out = []
    for _, i in sorted(decoding, reverse=True)[:n]:
        s = engine.slots[i]
        tokens = (tuple(s.req.prompt) + tuple(s.emitted))[:s.length]
        assert len(tokens) == s.length, (len(tokens), s.length)
        layers = (engine.cache.state.shape[0]
                  if all_layers_of_first and not out else 1)
        out.append((tokens, [np.asarray(engine.cache.state[li, i])
                             for li in range(layers)]))
    return out


def check(state, run):
    ref = _base()._Run(run).lib("reference")
    spec = run.cell.spec["check"]
    engine, serve = state["engine"], state["serve"]
    params, dims = state["params"], state["dims"]
    streams = _slot_states(engine, int(spec["state_streams"]), run.control)
    checked = _base().check(state, run)         # frees the engine's cache
    t0 = run.clock()
    limit = spec["limits"].get("state_gap")
    if not streams:
        checked["compared"].append({"name": "state_streams", "value": 0,
                                    "limit": 1, "ok": False})
        checked["correct"] = False
        return checked
    kinds = dims["kinds"]
    got = ref.state_gaps(params, dims, streams, serve.max_context)
    checked["compared"].append({
        "name": "state_gap", "value": got["widest"], "limit": limit,
        "ok": limit is None or got["widest"] <= limit})
    checked["correct"] = bool(checked["correct"]
                              and checked["compared"][-1]["ok"])
    notes = checked["notes"]
    notes["state"] = {"tokens": [len(t) for t, _ in streams],
                      "first_layer": [p[0] for p in got["per_stream"]]}
    if run.control:
        # every state layer of the first slot (the blocks in between too)
        last = max(li for li, k in enumerate(kinds) if k == "kda") + 1
        notes["state"]["layers_of_first"] = ref.state_gaps(
            params, dims, streams[:1], serve.max_context,
            layers=last)["per_stream"][0]
        notes.setdefault("control", {})["state_gap"] = ref.state_gaps(
            params, dims, streams, serve.max_context,
            control=spec["state_control"])["widest"]
    notes["state"]["reference_s"] = round(run.clock() - t0, 3)
    return checked


def close(state):
    _base().close(state)
