"""Driver of the serving cells whose layers keep a state-space state by
SLOT: ``drivers/serve_state.py``'s run to the letter (the cell's own
``reference`` file, the slowest step kept, the ``check.state_streams``
slots that decoded longest read back after the window and their FIRST
state layer held to the reference's token-by-token recurrence:
``state_gap``), with a check of its own only because that file's
``--control 1`` path looks the state layers up under the name ``'kda'``.
Here the reference says which layers keep a state (``state_gaps(...,
layers=<all>)`` returns one gap for each), so nothing names a mixer.
"""

from __future__ import annotations

import importlib.util
import os
import sys


def _state():
    name = "benchdriver_serve_state"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "serve_state.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


def build(run):
    return _state().build(run)


def measure(state, run):
    return _state().measure(state, run)


def check(state, run):
    base = _state()._base()
    ref = base._Run(run).lib("reference")
    spec = run.cell.spec["check"]
    engine, serve = state["engine"], state["serve"]
    params, dims = state["params"], state["dims"]
    streams = _state()._slot_states(engine, int(spec["state_streams"]),
                                    run.control)
    checked = base.check(state, run)            # frees the engine's cache
    t0 = run.clock()
    limit = spec["limits"].get("state_gap")
    if not streams:
        checked["compared"].append({"name": "state_streams", "value": 0,
                                    "limit": 1, "ok": False})
        checked["correct"] = False
        return checked
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
        notes["state"]["layers_of_first"] = ref.state_gaps(
            params, dims, streams[:1], serve.max_context,
            layers=dims["layers"])["per_stream"][0]
        notes.setdefault("control", {})["state_gap"] = ref.state_gaps(
            params, dims, streams, serve.max_context,
            control=spec["state_control"])["widest"]
    notes["state"]["reference_s"] = round(run.clock() - t0, 3)
    return checked


def close(state):
    _state().close(state)
